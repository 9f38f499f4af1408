"""PaME exchange (``repro.core.pame.pame_step`` -> ``repro.core.pme``):
device milliseconds per round of the program's own chunk under the
``pame.select`` and ``pame.exchange`` scopes (neighbour selection, masks,
the PME average), by self time (``probes/round_scopes.py``)."""
from bench import scopes


def read(ctx):
    return scopes.round_ms(ctx, "pame.select", "pame.exchange")
