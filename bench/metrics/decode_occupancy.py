"""Share of the decode chunk's slot-steps that decoded a served token:
decoded tokens over decode chunks x chunk steps x slots (engine
counters)."""


def read(ctx):
    chunks = ctx.stats["decode_chunks"]
    if not chunks:
        return None
    decoded = sum(len(r.output) - 1 for r in ctx.done)
    return 100.0 * decoded / (chunks * ctx.chunk_steps * ctx.n_slots)
