"""Reduction of a profiler trace (``.xplane.pb``) to device times.

The trace holds one plane per chip (``/device:TPU:<n>``) with a line of
program executions (``XLA Modules``) and a line of operations (``XLA
Ops``), and a host plane whose thread lines hold what JAX records on
the host (dispatch of each jitted function, transfers, compiles) and
the benchmark's ``bench_window`` annotation, which bounds the window.

Busy time is the union of the operation intervals on a chip inside the
window; idle share is one minus busy over the window.  Program and
kernel times are sums of event durations.  Every figure is averaged
over the chips the run used.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

WINDOW = "bench_window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULES, OPS = "XLA Modules", "XLA Ops"
# on the CPU (tests only) operations run on host threads of this name
CPU_OPS_LINE = "tf_XLAPjRtCpuClient"


@dataclasses.dataclass
class Events:
    """Events of one line: names and [start, end) in ns, sorted."""

    names: list
    starts: list
    ends: list

    def __len__(self):
        return len(self.names)


@dataclasses.dataclass
class Trace:
    window: tuple            # (start, end) ns of the benchmark's window
    modules: list            # per chip: Events of program executions
    ops: list                # per chip: Events of operations
    host: Events             # host events (every thread), sorted

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def op_name(text: str) -> str:
    """The instruction name of an operation event, whose name is its HLO
    text (``%paged_decode_attention.9 = bf16[...] custom-call(...)``)."""
    return text.split(" = ", 1)[0].lstrip("%")


def _collect(events, clip=None, name=lambda n: n) -> Events:
    rows = []
    for e in events:
        s = float(e.start_ns)
        t = s + float(e.duration_ns)
        if clip is not None and (t <= clip[0] or s >= clip[1]):
            continue
        rows.append((s, t, name(e.name)))
    rows.sort()
    return Events([r[2] for r in rows], [r[0] for r in rows],
                  [r[1] for r in rows])


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"{len(paths)} .xplane.pb files under "
                                f"{trace_dir}")
    return paths[0]


def load(source) -> Trace:
    """Read the planes this reduction needs from ``source``: the path of
    an ``.xplane.pb`` or a ``jax.profiler.ProfileData``."""
    from jax.profiler import ProfileData

    pd = (ProfileData.from_file(str(source))
          if isinstance(source, (str, os.PathLike)) else source)
    path = source if isinstance(source, (str, os.PathLike)) else "trace"
    host_lines, devices = [], []
    for plane in pd.planes:
        if plane.name == "/host:CPU":
            host_lines = list(plane.lines)
        elif DEVICE_PLANE.match(plane.name):
            devices.append(plane)
    window = None
    for line in host_lines:
        for e in line.events:
            if e.name == WINDOW:
                window = (float(e.start_ns),
                          float(e.start_ns) + float(e.duration_ns))
    if window is None:
        raise ValueError(f"no {WINDOW!r} annotation in {path}")
    host = _collect((e for ln in host_lines for e in ln.events
                     if e.name != WINDOW), window)
    if not devices:
        cpu = [e for ln in host_lines if ln.name.startswith(CPU_OPS_LINE)
               for e in ln.events if float(e.duration_ns) > 0
               and not e.name.startswith("ThreadpoolListener")]
        if not cpu:
            raise ValueError(f"no device plane in {path}")
        return Trace(window, [Events([], [], [])], [_collect(cpu, window)],
                     host)
    devices.sort(key=lambda p: int(p.name.rsplit(":", 1)[1]))
    modules, ops = [], []
    for plane in devices:
        lines = {ln.name: ln for ln in plane.lines}
        modules.append(_collect(lines[MODULES].events, window)
                       if MODULES in lines else Events([], [], []))
        ops.append(_collect(lines[OPS].events, window, op_name)
                   if OPS in lines else Events([], [], []))
    return Trace(window, modules, ops, host)


def _merged(ev: Events, window) -> list:
    """Union of the intervals of ``ev`` clipped to ``window``."""
    out = []
    lo, hi = window
    for s, t in zip(ev.starts, ev.ends):
        s, t = max(s, lo), min(t, hi)
        if t <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return out


def busy_s(tr: Trace) -> float:
    """Seconds in which an operation ran, averaged over the chips."""
    per = [sum(t - s for s, t in _merged(ev, tr.window)) * 1e-9
           for ev in tr.ops]
    return sum(per) / len(per)


def program_times(tr: Trace, part: str) -> list:
    """Durations (s) of every execution of the programs whose name
    holds ``part``, on every chip."""
    return [(t - s) * 1e-9 for ev in tr.modules
            for n, s, t in zip(ev.names, ev.starts, ev.ends) if part in n]


def op_seconds(tr: Trace, pattern: str) -> float:
    """Seconds of the operations whose instruction name matches
    ``pattern``, averaged over the chips."""
    rx = re.compile(pattern)
    per = [sum(t - s for n, s, t in zip(ev.names, ev.starts, ev.ends)
               if rx.search(n)) * 1e-9 for ev in tr.ops]
    return sum(per) / len(per)


_SUFFIX = re.compile(r"(\.\d+)+$")
# operations that only hold others: their time is their contents'
CONTROL_FLOW = ("while", "conditional", "call")


def top_ops(tr: Trace, n: int = 10) -> list:
    """The ``n`` operation names (instance numbers dropped, loops that
    hold other operations left out) with the most device time, averaged
    over the chips: [[name, seconds], ...]."""
    tot: dict = {}
    for ev in tr.ops:
        for name, s, t in zip(ev.names, ev.starts, ev.ends):
            key = _SUFFIX.sub("", name)
            if key in CONTROL_FLOW:
                continue
            tot[key] = tot.get(key, 0.0) + (t - s) * 1e-9
    k = len(tr.ops)
    return [[name, secs / k] for name, secs in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(tr: Trace, n: int = 10) -> list:
    """The ``n`` longest gaps on chip 0 between operations inside the
    window, each named by what the host was doing in it: the shortest
    host event that covers at least half of the gap, else the one that
    overlaps it most (``"(no host event)"`` where none does):
    [[name, seconds], ...]."""
    busy = _merged(tr.ops[0], tr.window)
    edges = [tr.window[0]] + [x for iv in busy for x in iv] + [tr.window[1]]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    host = tr.host
    out = []
    for s, t in gaps[:n]:
        cover, most = None, (0.0, "(no host event)")
        for hn, hs, ht in zip(host.names, host.starts, host.ends):
            if hs >= t:
                break
            ov = min(ht, t) - max(hs, s)
            if ov <= 0:
                continue
            if ov >= 0.5 * (t - s) and (cover is None
                                        or ht - hs < cover[0]):
                cover = (ht - hs, hn)
            if ov > most[0]:
                most = (ov, hn)
        out.append([cover[1] if cover else most[1], (t - s) * 1e-9])
    return out


def describe(path: str, top: int = 15) -> str:
    """A look at a trace by hand: every plane and line, its event count
    and its heaviest event names."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            tot, cnt, first = {}, 0, None
            lo, hi = None, None
            for e in line.events:
                cnt += 1
                d = float(e.duration_ns)
                tot[e.name] = tot.get(e.name, 0.0) + d
                s = float(e.start_ns)
                lo = s if lo is None else min(lo, s)
                hi = s + d if hi is None else max(hi, s + d)
                if first is None:
                    first = (e.name, dict(e.stats))
            out.append(f"  LINE {line.name!r}: {cnt} events, span "
                       f"[{lo}, {hi}] ns")
            if first:
                out.append(f"    first: {first[0]!r} stats {first[1]}")
            for name, d in sorted(tot.items(), key=lambda kv: -kv[1])[:top]:
                out.append(f"    {d * 1e-6:12.3f} ms  {name}")
    return "\n".join(out)
