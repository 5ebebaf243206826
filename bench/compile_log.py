"""Programs JAX obtains, seen through its monitoring events.

Each jitted program that is compiled or loaded from the persistent
cache records one backend-compile event (a cache hit costs only its
read).  ``CompileLog`` keeps the time and the function name of each,
so a run can count the programs obtained inside its measured window:
there should be none.
"""
from __future__ import annotations

import time

import jax

_COMPILE = "/jax/core/compile/backend_compile_duration"
_HIT = "/jax/compilation_cache/cache_hits"


class CompileLog:
    def __init__(self):
        self.events: list[tuple[float, str, float]] = []  # (t, fun, secs)
        self.hits = 0

    def _duration(self, name, secs, **kw):
        if name == _COMPILE:
            self.events.append((time.monotonic(), kw.get("fun_name", "?"),
                                secs))

    def _event(self, name, **_):
        if name == _HIT:
            self.hits += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)

    def between(self, t0: float, t1: float) -> list[tuple[float, str, float]]:
        """Programs obtained with monotonic time in ``[t0, t1]``."""
        return [e for e in self.events if t0 <= e[0] <= t1]
