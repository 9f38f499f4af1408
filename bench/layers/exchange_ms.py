"""PaME exchange (``repro.core.pame.pame_step`` -> ``repro.core.pme``):
device milliseconds per round of the cell's PaME step, bound through the
registry with the cell's hyperparameters and topology, with a quadratic
local loss (grad = w) in the model's place: neighbour selection, masks,
PME average, the local update and the round's metrics, without the
model's forward and backward."""


def read(ctx):
    probe = ctx.probe("exchange_alone")
    if probe["seconds_per_round"] is None:
        return None
    return 1000.0 * probe["seconds_per_round"]
