"""Whole round: the share of the device self time of the program's own
chunk that lies under no named scope, or in an operation the optimized
HLO's map lacks (``probes/round_scopes.py``); what the ``round_*`` metrics
cannot place."""
from bench import scopes


def read(ctx):
    probe = scopes.round_probe(ctx)
    if probe is None or not probe["round_ns"]:
        return None
    return 100.0 * probe["scope_ns"].get(None, 0.0) / probe["round_ns"]
