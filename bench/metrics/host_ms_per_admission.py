"""Host time of one admission: the self time of the ``engine.admit``
spans in the window (prompt to the device, page bookkeeping, the
prefill's dispatch, the stamps) less the blocking read of the first
token (``engine.first_token``), over the number of ``engine.admit``
spans.  Every admission stalls every decoding slot."""
from bench import spans as S


def read(ctx):
    n = len(S.spans(ctx.trace, S.ADMIT))
    if not n:
        return None
    return 1e3 * S.self_s(ctx.trace, S.ADMIT, (S.FIRST_TOKEN,)) / n
