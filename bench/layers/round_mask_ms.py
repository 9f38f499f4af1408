"""PaME exchange: device milliseconds per round of the program's own chunk
under the ``pme.mask`` scope (the coordinate masks' draws, inside the
exchange), by self time (``probes/round_scopes.py``)."""
from bench import scopes


def read(ctx):
    return scopes.round_ms(ctx, "pme.mask")
