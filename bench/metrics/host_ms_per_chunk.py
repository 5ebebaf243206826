"""Host time the synchronous loop puts between device programs, per
decode chunk: the self time of the ``engine.step`` spans in the window
outside admission, prefill chunks, the blocking read of the chunk's
tokens and the sleep until an arrival (decode dispatch, the per-slot
stitch, releases, scheduling and loop bookkeeping), over the number of
``engine.decode_sync`` spans."""
from bench import spans as S

EXCLUDED = (S.ADMIT, S.PREFILL_CHUNK, S.DECODE_SYNC, S.WAIT_ARRIVAL)


def read(ctx):
    n = len(S.spans(ctx.trace, S.DECODE_SYNC))
    if not n:
        return None
    return 1e3 * S.self_s(ctx.trace, S.STEP, EXCLUDED) / n
