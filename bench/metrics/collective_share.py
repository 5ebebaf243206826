"""Share of the device's busy time spent in the exchange between chips:
the collective operations (``bench.collectives``) of the whole window,
prefill and decode, over the time in which any operation ran, both
averaged over the chips."""
from bench import collectives as C
from bench import trace as T


def read(ctx):
    secs, count = C.seconds(ctx.trace)
    busy = T.busy_s(ctx.trace)
    if not count or not busy:
        return None
    return 100.0 * secs / busy
