"""Reduction of a profiler trace to device busy time, time per program and
kernel, the top device operations and the idle gaps by host span.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes. Device
planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one event per
executed operation and their ``XLA Modules`` line one per program run. Host
spans are the ``bench.*`` annotations of the benchmark's own loop, on the
same clock. The traced window is the ``bench.trace_window`` span.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Tuple

WINDOW_SPAN = "bench.trace_window"
_DEVICE = re.compile(r"^/device:TPU:\d+$")

Event = Tuple[float, float, str, str]      # start s, end s, name, module


@dataclass
class Trace:
    ops: Dict[str, List[Event]] = field(default_factory=dict)
    modules: Dict[str, List[Event]] = field(default_factory=dict)
    host: List[Event] = field(default_factory=list)


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _short(name: str) -> str:
    """An op event's name is its HLO instruction; keep the name part."""
    return name.split(" = ", 1)[0]


def _modules_of(ops: List[Tuple[float, float, str]],
                mods: List[Event]) -> List[Event]:
    """Give each op the program whose run on that device encloses it."""
    mods = sorted(mods)
    starts = [m[0] for m in mods]
    out = []
    for a, b, name in ops:
        i = bisect.bisect_right(starts, a) - 1
        mod = mods[i][2] if i >= 0 and mods[i][1] >= b else ""
        out.append((a, b, name, mod))
    return out


def read(path: str, host_prefix: str = "bench.") -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        if _DEVICE.match(plane.name):
            ops, mods = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [(e.start_ns * 1e-9,
                            (e.start_ns + e.duration_ns) * 1e-9,
                            _short(e.name)) for e in line.events]
                elif line.name == "XLA Modules":
                    mods = [(e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9,
                             re.sub(r"\(\d+\)$", "", e.name), "")
                            for e in line.events]
            tr.ops[plane.name] = _modules_of(ops, mods)
            tr.modules[plane.name] = [(a, b, n, n) for a, b, n, _ in mods]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                tr.host.extend((e.start_ns * 1e-9,
                                (e.start_ns + e.duration_ns) * 1e-9, e.name,
                                "") for e in line.events
                               if e.name.startswith(host_prefix))
    return tr


def window(tr: Trace) -> Tuple[float, float]:
    spans = [(a, b) for a, b, n, _ in tr.host if n == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"trace holds no {WINDOW_SPAN} span")
    return min(a for a, _ in spans), max(b for _, b in spans)


def _clip(evs, t0: float, t1: float):
    return [(max(a, t0), min(b, t1), n, m) for a, b, n, m in evs
            if b > t0 and a < t1]


def _union(evs) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b, *_ in sorted(evs):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_s(tr: Trace, t0: float, t1: float) -> float:
    """Seconds in which some operation ran, averaged over the devices."""
    if not tr.ops:
        return 0.0
    per = [sum(b - a for a, b in _union(_clip(evs, t0, t1)))
           for evs in tr.ops.values()]
    return sum(per) / len(per)


def time_where(tr: Trace, t0: float, t1: float,
               pred: Callable[[str, str], bool], modules: bool = False
               ) -> Tuple[float, int]:
    """Device seconds and count of the op (or program) events for which
    ``pred(name, module)`` holds, summed over devices; overlapping events
    of one device count once."""
    src = tr.modules if modules else tr.ops
    total, count = 0.0, 0
    for evs in src.values():
        hit = [e for e in _clip(evs, t0, t1) if pred(e[2], e[3])]
        total += sum(b - a for a, b in _union(hit))
        count += len(hit)
    return total, count


def top_ops(tr: Trace, t0: float, t1: float, n: int = 10):
    acc: Dict[str, float] = defaultdict(float)
    for evs in tr.ops.values():
        for a, b, name, mod in _clip(evs, t0, t1):
            acc[f"{mod}:{name}" if mod else name] += b - a
    return sorted(([k, v] for k, v in acc.items()), key=lambda kv: -kv[1])[:n]


def idle_gaps(tr: Trace, t0: float, t1: float, n: int = 10):
    """Idle device time, summed by the host span that covers most of each
    gap (``host.other`` where no span does), longest first."""
    spans = [(a, b, name) for a, b, name, _ in tr.host if name != WINDOW_SPAN]
    acc: Dict[str, float] = defaultdict(float)
    for evs in tr.ops.values():
        busy = _union(_clip(evs, t0, t1))
        edges = [t0] + [x for ab in busy for x in ab] + [t1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            best, label = 0.0, "host.other"
            for sa, sb, name in spans:
                ov = min(b, sb) - max(a, sa)
                if ov > best:
                    best, label = ov, name
            acc[label] += (b - a) / len(tr.ops)
    return sorted(([k, v] for k, v in acc.items()), key=lambda kv: -kv[1])[:n]


def reduce(tr: Trace) -> dict:
    t0, t1 = window(tr)
    return {"window_s": t1 - t0, "busy_s": busy_s(tr, t0, t1),
            "device_ops": top_ops(tr, t0, t1),
            "idle_gaps": idle_gaps(tr, t0, t1),
            "t0": t0, "t1": t1, "devices": len(tr.ops)}


def describe(tr: Trace) -> dict:
    """What the trace holds, for a reader who has to find names in it."""
    mods: Dict[str, int] = defaultdict(int)
    for evs in tr.modules.values():
        for _, _, name, _ in evs:
            mods[name] += 1
    per: Dict[str, Dict[str, float]] = defaultdict(lambda: defaultdict(float))
    for evs in tr.ops.values():
        for a, b, name, mod in evs:
            per[mod][name] += b - a
    top = {m: sorted(([k, v] for k, v in d.items()),
                     key=lambda kv: -kv[1])[:3] for m, d in per.items()}
    return {"devices": sorted(tr.ops), "op_events": sum(map(len,
                                                           tr.ops.values())),
            "modules": dict(mods), "top_ops_by_module": top,
            "host_spans": sorted({n for _, _, n, _ in tr.host})}
