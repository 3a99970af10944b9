"""Metrics that read the program's own spans (``s2ce.*``, see
``repro.core.spans``) and named programs in the trace of a ``--trace 1``
run, beside the device's ops on the same clock.

The trace is parsed once per run and kept with it. The window is the run's
``bench.trace_window``, as the trace reduction found it. A program
without these spans or names gives None, never an error.

    python bench/progtrace.py <trace directory>

prints the device's idle time in the traced window by the innermost
``s2ce.*`` span that covers it.
"""

from __future__ import annotations

import bisect
import pathlib
import statistics
import sys
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

if __name__ == "__main__":
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench import devtrace, harness  # noqa: E402

# the program's span prefix (``repro.core.spans.PREFIX``), written out so
# that this file also reads a program that has no such module
PREFIX = "s2ce."
BATCH_SPAN = PREFIX + "execute_batch"
DECODE_SPAN = PREFIX + "serve.decode_step"
UNCOVERED = "(no s2ce span)"


def load(run) -> Optional[devtrace.Trace]:
    """The run's trace with the program's spans as its host events; None
    for a run without a trace."""
    if run.trace_result is None:
        return None
    if "program_trace" not in run.traced:
        root = run.cell.bench_dir.parent
        path = devtrace.find_xplane(
            str(harness.trace_dir(root, run.cell.name)))
        run.traced["program_trace"] = devtrace.read(path, host_prefix=PREFIX)
    return run.traced["program_trace"]


def _window(run) -> Tuple[float, float]:
    return run.trace_result["t0"], run.trace_result["t1"]


def spans_in(tr: devtrace.Trace, name: str, t0: float, t1: float
             ) -> List[Tuple[float, float]]:
    """The ``name`` spans that start in ``[t0, t1)``."""
    return [(a, b) for a, b, n, _ in tr.host if n == name and t0 <= a < t1]


def _overlap(xs, ys) -> float:
    """Length of the intersection of two sorted, disjoint interval lists."""
    i = j = 0
    total = 0.0
    while i < len(xs) and j < len(ys):
        lo, hi = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        total += max(0.0, hi - lo)
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_inside(tr: devtrace.Trace, name: str, t0: float, t1: float
                ) -> Optional[float]:
    """Seconds of the window in which some ``name`` span is open and no
    operation runs on the device, averaged over the devices; None where
    no such span lies in the window."""
    covered = devtrace._union(devtrace._clip(
        [(a, b, "", "") for a, b, n, _ in tr.host if n == name], t0, t1))
    if not covered or not tr.ops:
        return None
    span_s = sum(b - a for a, b in covered)
    per = [span_s - _overlap(covered, devtrace._union(
        devtrace._clip(evs, t0, t1))) for evs in tr.ops.values()]
    return sum(per) / len(per)


def dispatch_idle_pct(run) -> Optional[float]:
    """100 x the device's idle time inside ``s2ce.execute_batch`` spans
    over the traced window."""
    tr = load(run)
    if tr is None:
        return None
    t0, t1 = _window(run)
    idle = idle_inside(tr, BATCH_SPAN, t0, t1)
    return None if idle is None else 100.0 * idle / (t1 - t0)


def program_ms_per_item(run, program: str) -> Optional[float]:
    """Device milliseconds of the ``program`` runs in the traced window
    per traced item (a batch or a wave)."""
    tr = load(run)
    items = len(run.traced.get("items", ()))
    if tr is None or not items:
        return None
    t0, t1 = _window(run)
    secs, n = devtrace.time_where(tr, t0, t1,
                                  lambda name, mod: name == program,
                                  modules=True)
    return 1e3 * secs / items if n else None


def span_median_ms(run, name: str) -> Optional[float]:
    """Median length of the ``name`` spans that start in the traced
    window, in milliseconds."""
    tr = load(run)
    if tr is None:
        return None
    got = [b - a for a, b in spans_in(tr, name, *_window(run))]
    return 1e3 * statistics.median(got) if got else None


def idle_by_innermost(tr: devtrace.Trace, t0: float, t1: float
                      ) -> List[List]:
    """Idle device seconds in ``[t0, t1)`` by the innermost ``s2ce.*``
    span open over them (the latest to open), averaged over the devices,
    longest first."""
    if not tr.ops:
        return []
    spans = sorted((max(a, t0), min(b, t1), n) for a, b, n, _ in tr.host
                   if n.startswith(PREFIX) and b > t0 and a < t1)
    cuts = sorted({t0, t1, *(x for a, b, _ in spans for x in (a, b))})
    # the innermost span over each piece between consecutive cuts
    label, open_ = [], []
    k = 0
    for lo in cuts[:-1]:
        while k < len(spans) and spans[k][0] <= lo:
            open_.append(spans[k])
            k += 1
        open_ = [s for s in open_ if s[1] > lo]
        label.append(open_[-1][2] if open_ else UNCOVERED)
    acc: Dict[str, float] = defaultdict(float)
    for evs in tr.ops.values():
        busy = devtrace._union(devtrace._clip(evs, t0, t1))
        edges = [t0] + [x for ab in busy for x in ab] + [t1]
        for a, b in zip(edges[0::2], edges[1::2]):
            i = bisect.bisect_right(cuts, a) - 1
            while a < b:
                hi = min(b, cuts[i + 1])
                acc[label[i]] += (hi - a) / len(tr.ops)
                a, i = hi, i + 1
    return sorted(([k, v] for k, v in acc.items()), key=lambda kv: -kv[1])


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    if len(args) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    path = devtrace.find_xplane(args[0])
    t0, t1 = devtrace.window(devtrace.read(path))
    tr = devtrace.read(path, host_prefix=PREFIX)
    if not tr.ops:
        print(f"no device planes in {path}")
        return 1
    rows = idle_by_innermost(tr, t0, t1)
    idle = sum(v for _, v in rows)
    print(f"window {t1 - t0:.6f} s, device idle {idle:.6f} s "
          f"({100 * idle / (t1 - t0):.3f}%)")
    for name, secs in rows:
        print(f"{secs:12.6f} s  {100 * secs / (t1 - t0):7.3f}%  {name}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
