"""Engine (``repro.core.engine`` chunk loop): device-idle milliseconds per
chunk of the program's own traced chunks inside the engine's host spans
``engine.batches``, ``engine.stack``, ``engine.dispatch`` and
``engine.readback``, each gap named by the innermost span around its
middle (``probes/round_scopes.py``)."""
from bench import scopes


def read(ctx):
    probe = scopes.round_probe(ctx)
    if probe is None or probe["gaps"] is None or not probe["chunks"]:
        return None
    idle = sum(s for name, s in probe["gaps"] if name in scopes.ENGINE_SPANS)
    return 1000.0 * idle / probe["chunks"]
