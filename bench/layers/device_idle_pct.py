"""Device: the share of the traced window in which no operation ran on the
chip, 1 - (union of the device's operation intervals) / window."""
from bench import xplane


def read(ctx):
    busy = xplane.busy_s(ctx.trace, *ctx.window)
    if busy is None:
        return None
    return 100.0 * (1.0 - busy * 1e9 / (ctx.window[1] - ctx.window[0]))
