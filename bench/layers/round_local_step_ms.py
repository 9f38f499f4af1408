"""Model (``repro.models`` forward and backward): device milliseconds per
round of the program's own chunk under the ``pame.local_step`` scope
(``jax.vmap`` of the gradient over the m nodes), by self time
(``probes/round_scopes.py``)."""
from bench import scopes


def read(ctx):
    return scopes.round_ms(ctx, "pame.local_step")
