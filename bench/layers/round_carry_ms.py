"""Engine (``repro.core.engine`` scan body): device milliseconds per round
of the program's own chunk under the ``engine.carry`` scope (termination
window, freeze selects over state and aux, the round's outputs), by self
time; a select fused into another scope's work counts there
(``probes/round_scopes.py``)."""
from bench import scopes


def read(ctx):
    return scopes.round_ms(ctx, "engine.carry")
