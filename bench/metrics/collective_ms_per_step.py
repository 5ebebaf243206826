"""Device time of the exchange between chips in one decode step: the
collective operations (``bench.collectives``) that lie inside the
decode-chunk program's executions, averaged over the chips, over the
executions times the chunk's steps.  At tensor parallelism every layer
ends its attention and its feed-forward block in an all-reduce."""
from bench import collectives as C

PROGRAM = "_decode_chunk_impl"


def read(ctx):
    secs, count, runs = C.in_programs(ctx.trace, PROGRAM)
    if not count or not runs:
        return None
    return 1e3 * secs / (runs * ctx.chunk_steps)
