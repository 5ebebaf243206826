"""Reduction of the engine's host spans in a trace.

``ContinuousBatchingEngine.serve`` writes ``jax.profiler.TraceAnnotation``
spans onto the trace's host plane, on the same clock as the device
planes: ``engine.step`` for one iteration of its loop and, inside it,
``engine.admit`` (around ``engine.first_token``), ``engine.prefill_chunk``,
``engine.decode`` (around ``engine.decode_sync``), ``engine.stitch`` and
``engine.wait_arrival``.  ``bench.trace.load`` keeps every host event
that overlaps the window; here spans are clipped to the window and
nested by interval containment.  A trace of a program without these
spans has none, and every figure here is then empty.
"""
from __future__ import annotations

import bisect

from bench import trace as T

PREFIX = "engine."
STEP = "engine.step"
ADMIT = "engine.admit"
FIRST_TOKEN = "engine.first_token"
PREFILL_CHUNK = "engine.prefill_chunk"
DECODE = "engine.decode"
DECODE_SYNC = "engine.decode_sync"
STITCH = "engine.stitch"
WAIT_ARRIVAL = "engine.wait_arrival"
OUTSIDE = "(outside engine spans)"


def spans(tr: T.Trace, name: str) -> list:
    """[(start, end)] ns of the spans named ``name``, clipped to the
    window, in order of start."""
    lo, hi = tr.window
    h = tr.host
    return [(max(s, lo), min(e, hi))
            for n, s, e in zip(h.names, h.starts, h.ends) if n == name]


def _union_ns(intervals) -> float:
    """Length of the union of intervals sorted by start."""
    total, end = 0.0, None
    for s, e in intervals:
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def self_s(tr: T.Trace, name: str, minus=()) -> float:
    """Seconds of the ``name`` spans that no span named in ``minus``
    lying inside one of them covers."""
    kids = sorted(iv for m in minus for iv in spans(tr, m))
    starts = [s for s, _ in kids]
    total = 0.0
    for s, e in spans(tr, name):
        i = bisect.bisect_left(starts, s)
        j = bisect.bisect_left(starts, e)
        total += (e - s) - _union_ns(iv for iv in kids[i:j] if iv[1] <= e)
    return total * 1e-9


def innermost(tr: T.Trace) -> list:
    """[(start, end, name)]: the union of the engine spans in the window
    cut into pieces, each named by the innermost span that covers it
    (the spans of one thread nest), in order of start."""
    lo, hi = tr.window
    h = tr.host
    evs = sorted(((max(s, lo), min(e, hi), n)
                  for n, s, e in zip(h.names, h.starts, h.ends)
                  if n.startswith(PREFIX)), key=lambda x: (x[0], -x[1]))
    out, stack, t = [], [], lo

    def close_until(x):
        nonlocal t
        while stack and stack[-1][0] <= x:
            end, n = stack.pop()
            if end > t:
                out.append((t, end, n))
                t = end

    for s, e, n in evs:
        close_until(s)
        if stack and s > t:
            out.append((t, s, stack[-1][1]))
        stack.append((e, n))
        t = max(t, s)
    close_until(float("inf"))
    return out


def idle_by_span(tr: T.Trace) -> dict:
    """{name: seconds} of the device's idle time in the window (no
    operation running), each piece given to the innermost engine span
    that covers it, the rest to ``OUTSIDE``; averaged over the chips."""
    pieces = innermost(tr)
    out: dict = {}
    for ev in tr.ops:
        busy = T._merged(ev, tr.window)
        edges = [tr.window[0]] + [x for iv in busy for x in iv] \
            + [tr.window[1]]
        idle = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        inside, i = 0.0, 0
        for s, e in idle:
            while i < len(pieces) and pieces[i][1] <= s:
                i += 1
            k = i
            while k < len(pieces) and pieces[k][0] < e:
                ov = min(e, pieces[k][1]) - max(s, pieces[k][0])
                if ov > 0:
                    out[pieces[k][2]] = out.get(pieces[k][2], 0.0) + ov
                    inside += ov
                k += 1
        out[OUTSIDE] = out.get(OUTSIDE, 0.0) + \
            sum(e - s for s, e in idle) - inside
    k = len(tr.ops)
    return {n: v * 1e-9 / k for n, v in out.items()}
