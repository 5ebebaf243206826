"""Device time of the prefill-into-slot programs in the trace, per
thousand prompt tokens prefilled in the window (averaged over chips)."""
from bench import trace as T

PROGRAM = "_prefill_slot_impl"


def read(ctx):
    times = T.program_times(ctx.trace, PROGRAM)
    tokens = sum(len(r.prompt) for r in ctx.done)
    if not times or not tokens:
        return None
    return 1e3 * sum(times) / ctx.chips / tokens * 1e3
