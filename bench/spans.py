"""Host spans and counters recorded from the benchmark's own loop.

Every span is also a ``jax.profiler.TraceAnnotation``, so in a traced run
the profiler's trace holds it on the same clock as the device's ops and
the trace reduction can say what the host was doing in each idle gap.
"""

from __future__ import annotations

import contextlib
import time
from collections import defaultdict
from typing import Dict, List, Tuple

import jax


class Spans:
    def __init__(self):
        self.intervals: Dict[str, List[Tuple[float, float]]] = \
            defaultdict(list)

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            try:
                yield
            finally:
                self.intervals[name].append((t0, time.perf_counter()))

    def total(self, name: str, t0: float = float("-inf"),
              t1: float = float("inf")) -> Tuple[float, int]:
        """Seconds and count of the ``name`` spans that start in
        ``[t0, t1)``."""
        iv = [(a, b) for a, b in self.intervals.get(name, ())
              if t0 <= a < t1]
        return sum(b - a for a, b in iv), len(iv)


class CompileCounter:
    """Counts JAX's compile events (tracing, lowering and backend compile
    each report one) so a window can show that nothing compiled in it."""

    PREFIX = "/jax/core/compile/"

    def __init__(self):
        self.events: List[Tuple[float, str]] = []
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, name, secs, **kw):
        if name.startswith(self.PREFIX):
            self.events.append((time.perf_counter(), name))

    def count(self, t0: float, t1: float) -> int:
        return sum(1 for t, _ in self.events if t0 <= t < t1)
