"""Whole decode step's share of the chip's peak: the model FLOPs of
the tokens decoded (2 x matmul parameters per token, plus attention
over each token's context) over the decode-chunk programs' device time
x chips x peak bf16 FLOP/s."""
from bench import counts as C
from bench import trace as T

PROGRAM = "_decode_chunk_impl"


def read(ctx):
    times = T.program_times(ctx.trace, PROGRAM)
    if not times or ctx.peaks is None:
        return None
    per_chip_s = sum(times) / ctx.chips
    flops = sum(C.decode_token_flops(ctx.cfg, c) for r in ctx.done
                for c in C.decode_contexts(len(r.prompt), len(r.output)))
    return 100.0 * flops / (per_chip_s * ctx.chips
                            * ctx.peaks["bf16_flops"])
