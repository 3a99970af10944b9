"""Arithmetic the metric files share: rates and tails over the window,
span means, and device time from the trace reduction."""

from __future__ import annotations

import math
from typing import Callable, Optional

from bench import devtrace
from bench.harness import Run, percentile


def window_s(run: Run) -> float:
    return run.t1 - run.t0


def rate(run: Run, size: str) -> Optional[float]:
    """Sum of ``size`` over the items completed in the window, per second
    of the window."""
    done = run.completed()
    if not done:
        return None
    return sum(it.sizes[size] for it in done) / window_s(run)


def latency_p95(run: Run) -> Optional[float]:
    """95th percentile, over every item due in the window, of done minus
    due, in seconds; an item never done counts as infinitely late."""
    if not run.items:
        return None
    lat = [(it.done - it.due) if it.ok else math.inf for it in run.items]
    return percentile(lat, 0.95)


def span_ms(run: Run, name: str) -> Optional[float]:
    total, n = run.spans.total(name, run.t0, run.t1)
    return 1e3 * total / n if n else None


def idle_pct(run: Run) -> Optional[float]:
    tr = run.trace_result
    if tr is None or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def device_time(run: Run, pred: Callable[[str, str], bool],
                modules: bool = False) -> Optional[float]:
    """Device seconds of the matching ops (or programs) in the traced
    window; None where nothing matches."""
    tr = run.trace_result
    if tr is None or run.trace_obj is None:
        return None
    secs, n = devtrace.time_where(run.trace_obj, tr["t0"], tr["t1"], pred,
                                  modules=modules)
    return secs if n else None
