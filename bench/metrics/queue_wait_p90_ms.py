"""p90 over the served requests of the queue wait, from the request's
due arrival to the admission that took it into a slot (the engine's
``admitted_s`` stamp, on the serve clock).  TTFT is this wait plus the
admission itself."""
import numpy as np


def read(ctx):
    waits = [r.admitted_s - r.arrival_s for r in ctx.done
             if getattr(r, "admitted_s", None) is not None]
    if not waits:
        return None
    return 1e3 * float(np.percentile(np.asarray(waits, float), 90))
