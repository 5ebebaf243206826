"""Device time of one decode step: the decode-chunk program's
executions in the trace, per execution, over the chunk's steps."""
from bench import trace as T

PROGRAM = "_decode_chunk_impl"


def read(ctx):
    times = T.program_times(ctx.trace, PROGRAM)
    if not times:
        return None
    return 1e3 * sum(times) / len(times) / ctx.chunk_steps
