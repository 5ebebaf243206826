"""Share of its roofline that the paged decode attention kernel
reaches: the least time its algorithmic work needs at the chip's
peaks, over the kernel's summed device time (both per chip).

The work is what attention needs for the tokens served: for every
decode step of every request, each layer reads the K and V rows of
the request's live context (its chip's KV heads), its query and writes
its output; the FLOPs are the scores and the weighted values.  Slots
that hold no request, pages past a sequence's end and the layout
changes around the kernel are not work.  At these shapes every call is
bound by HBM (2 FLOPs per byte against the chip's ~240)."""
from bench import counts as C
from bench import trace as T

KERNEL = r"paged_decode"


def read(ctx):
    secs = T.op_seconds(ctx.trace, KERNEL)
    if not secs or ctx.peaks is None:
        return None
    cfg, layers = ctx.cfg, ctx.cfg["num_hidden_layers"]
    flops = nbytes = 0
    for r in ctx.done:
        ctxs = list(C.decode_contexts(len(r.prompt), len(r.output)))
        f, b = C.paged_attn_call(cfg, ctxs, shards=ctx.chips)
        flops += f * layers
        nbytes += b * layers
    least = max(flops / ctx.peaks["bf16_flops"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / secs
