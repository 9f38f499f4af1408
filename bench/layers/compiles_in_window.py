"""Engine (``repro.core.engine`` scan chunks): executables obtained (compiled
or read from the persistent cache) inside the measured window; 0 when
set-up warmed up every shape the window uses."""


def read(ctx):
    return float(ctx.compiles)
