"""Collective operations (the exchange between chips) in a reduced trace.

An operation event of the ``XLA Ops`` line is known by its instruction
name alone (``bench.trace.op_name``): the v5e's events carry no
``op_name`` metadata.  A collective is an instruction whose name holds
the name of a collective opcode (``all-reduce``, its asynchronous
``all-reduce-start`` / ``all-reduce-done`` halves, ``all-gather``,
``reduce-scatter``, ``collective-permute``, ``all-to-all``, and a fusion
named after one of them) or of the JAX primitive XLA names the
instruction after: a ``lax.psum`` compiles to an ``all-reduce``
instruction named ``psum.<n>``.
"""
from __future__ import annotations

import bisect
import re

COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|all_reduce|all_gather|reduce_scatter|all_to_all"
    r"|psum|pmax|pmin|ppermute")


def is_collective(name: str) -> bool:
    return COLLECTIVE.search(name) is not None


def per_execution(modules, ops, part: str) -> list:
    """[events, seconds] of the collective operations inside each
    execution of a program whose name holds ``part``, on one chip."""
    runs = [(s, t) for n, s, t in zip(modules.names, modules.starts,
                                      modules.ends) if part in n]
    starts = [s for s, _ in runs]
    out = [[0, 0.0] for _ in runs]
    for n, s, t in zip(ops.names, ops.starts, ops.ends):
        if not is_collective(n):
            continue
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and t <= runs[i][1]:
            out[i][0] += 1
            out[i][1] += (t - s) * 1e-9
    return out


def in_programs(tr, part: str) -> tuple[float, float, float]:
    """Seconds and number of the collective operations inside executions
    of the programs whose name holds ``part``, and the number of those
    executions, each averaged over the chips."""
    per = [per_execution(m, o, part) for m, o in zip(tr.modules, tr.ops)]
    k = len(per)
    return (sum(secs for p in per for _, secs in p) / k,
            sum(n for p in per for n, _ in p) / k,
            sum(len(p) for p in per) / k)


def seconds(tr) -> tuple[float, float]:
    """Seconds and number of every collective operation in the window,
    averaged over the chips."""
    secs, count = 0.0, 0
    for ev in tr.ops:
        for n, s, t in zip(ev.names, ev.starts, ev.ends):
            if is_collective(n):
                secs += (t - s) * 1e-9
                count += 1
    return secs / len(tr.ops), count / len(tr.ops)
