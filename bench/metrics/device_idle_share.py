"""Share of the traced window in which no operation ran on the device
(averaged over chips)."""
from bench import trace as T


def read(ctx):
    return 100.0 * (1.0 - T.busy_s(ctx.trace) / ctx.trace.window_s)
