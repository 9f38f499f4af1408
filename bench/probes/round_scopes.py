"""The program's own chunk, traced: a fresh state from the run's seeds fed
by the program's ``make_batch`` from round ``chunk`` on (the window's state
is gone when the readers run), one chunk untraced, then ``CHUNKS`` chunks
in a profiler session of its own.  Device self time inside the chunk
program's runs is split by the program's named scopes, through the
optimized HLO the runner gives (``bench/scopes.py``), and each device-idle
gap inside a chunk's ``engine.chunk`` span is named by the program's host
span around it.

A program without the runner's HLO accessor or without host spans (one
that predates them) gives nothing to read."""
import shutil
import tempfile

from bench import harness, scopes, xplane

CHUNKS = 2
PROGRAM = "jit_chunk("


def measure(ctx):
    prog = ctx.program
    if not hasattr(prog.runner, "optimized_hlo"):
        return {"scope_ns": None, "round_ns": None, "gaps": None, "chunks": 0}
    state, aux = prog.start(ctx.seeds)
    feed = harness.Feed(prog.make_batch, ctx.seeds.data_offset, keep=0)
    k = prog.chunk
    state, aux, _ = prog.chunk_from(state, aux, feed, k)
    log_dir = tempfile.mkdtemp(prefix="bench-rounds-")
    try:
        with harness.profiled(log_dir):
            for _ in range(CHUNKS):
                k += prog.chunk
                state, aux, _ = prog.chunk_from(state, aux, feed, k)
        path = xplane.find(log_dir)
        trace, spans = xplane.load(path), scopes.host_spans(path)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    del state, aux
    rounds = CHUNKS * prog.chunk
    chunks = [span for span in spans if span[2] == "engine.chunk"]
    gaps = scopes.chunk_gaps(trace, spans) if chunks and trace.devices else None
    ops_ns = scopes.program_ops_ns(trace, PROGRAM)
    scope_ns = round_ns = None
    if ops_ns is not None:
        mapping = {}
        for text in prog.runner.optimized_hlo().values():
            mapping.update(scopes.scope_map(text))
        scope_ns = {path: ns / rounds
                    for path, ns in scopes.scope_ns(ops_ns, mapping).items()}
        round_ns = sum(scope_ns.values())
    return {"scope_ns": scope_ns, "round_ns": round_ns, "gaps": gaps,
            "chunks": len(chunks)}
