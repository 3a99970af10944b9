"""One run of one cell: set-up, the measured window, the device's peak
memory, the trace reduction, the metrics and the check against the plain
reference, in that order. ``run.py`` is the command-line face of
:func:`run_cell`; tests call it directly."""

from __future__ import annotations

import gc
import json
import math
import pathlib
import shutil
import sys
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from bench import cells, devtrace, roofline
from bench.spans import CompileCounter, Spans


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclass
class Item:
    """One unit of offered work as it went through the window."""
    index: int
    due: float                        # absolute, host clock
    sizes: Dict[str, int]
    start: Optional[float] = None
    done: Optional[float] = None
    ok: bool = False
    wave: Optional[int] = None


@dataclass
class Run:
    """What a run records; metric files read it."""
    cell: cells.Cell
    seed: int
    seconds: float
    trace: bool
    t_process: float
    spans: Spans = field(default_factory=Spans)
    compiles: Optional[CompileCounter] = None
    items: List[Item] = field(default_factory=list)
    t0: Optional[float] = None        # window opens (host clock)
    t1: Optional[float] = None        # window closes
    setup_s: Optional[float] = None
    device_kind: str = ""
    counters: Dict[str, Any] = field(default_factory=dict)
    trace_result: Optional[dict] = None
    trace_obj: Optional[devtrace.Trace] = None
    traced: Dict[str, Any] = field(default_factory=dict)

    def open_window(self) -> float:
        # what set-up left on the heap moves to a generation the collector
        # no longer walks, so a full collection over it cannot stall a
        # batch inside the window
        gc.collect()
        gc.freeze()
        self.t0 = time.perf_counter()
        self.setup_s = self.t0 - self.t_process
        return self.t0

    def close_window(self) -> None:
        """The window closes when the last item it started is done."""
        done = [it.done for it in self.items if it.done is not None]
        self.t1 = max(done) if done else time.perf_counter()
        gc.unfreeze()

    def peaks(self) -> Dict[str, float]:
        return roofline.peaks(self.device_kind)

    def completed(self) -> List[Item]:
        return [it for it in self.items if it.ok]


class Tracer:
    """Traces the first ``items`` items of the window (whole items, so
    what a trace-derived metric counts ran inside the trace) under a
    ``bench.trace_window`` span. A no-op without a trace directory."""

    def __init__(self, run: "Run", directory, items: int):
        self.run, self.dir = run, directory
        self.left = int(items) if directory is not None else 0
        self.span = None
        run.traced["items"] = []

    def before(self, item: Item) -> None:
        if self.left and self.span is None:
            import jax
            jax.profiler.start_trace(str(self.dir))
            self.span = self.run.spans.span(devtrace.WINDOW_SPAN)
            self.span.__enter__()

    def after(self, item: Item) -> None:
        if self.span is None or not self.left:
            return
        self.run.traced["items"].append(item)
        self.left -= 1
        if not self.left:
            self.close()

    def close(self) -> None:
        if self.span is not None:
            import jax
            self.span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.span = None
            self.left = 0


def percentile(values: List[float], q: float) -> float:
    """The ``q`` quantile by the nearest-rank rule; infinities count."""
    if not values:
        return math.inf
    s = sorted(values)
    return s[min(len(s) - 1, max(0, math.ceil(q * len(s)) - 1))]


def _device_info(jax, chips: int, require: bool) -> dict:
    devs = jax.devices()
    if require and (jax.default_backend() != "tpu" or len(devs) < chips):
        raise NoAccelerator(
            f"need {chips} TPU chip(s); JAX has backend "
            f"{jax.default_backend()!r} with {len(devs)} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _memory_peak(jax) -> Optional[int]:
    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def trace_dir(root: pathlib.Path, workload: str) -> pathlib.Path:
    return root / ".bench_out" / f"trace-{workload}"


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_process: float, root: pathlib.Path = cells.ROOT,
             require_accelerator: bool = True,
             cell: Optional[cells.Cell] = None, log=sys.stderr
             ) -> Dict[str, Any]:
    """Run ``workload`` once and return ``{"earlier": ..., "result": ...}``:
    the counters line and the result line, whose last key is ``checks``."""
    cell = cell or cells.load_cell(workload, root)
    import jax
    device = _device_info(jax, cell.chips, require_accelerator)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    run = Run(cell=cell, seed=int(seed), seconds=float(seconds),
              trace=bool(trace), t_process=t_process,
              device_kind=device["kind"], compiles=CompileCounter())
    driver = cell.driver()
    system = driver.setup(cell, run)
    tdir = None
    if trace:
        tdir = trace_dir(root, workload)
        shutil.rmtree(tdir, ignore_errors=True)
        tdir.mkdir(parents=True)
    driver.window(system, run, tdir)
    device["memory_peak_bytes"] = _memory_peak(jax)

    result: Dict[str, Any] = {}
    if trace:
        run.trace_obj = devtrace.read(devtrace.find_xplane(str(tdir)))
        run.trace_result = devtrace.reduce(run.trace_obj)
        device["busy_s"] = run.trace_result["busy_s"]
        device["window_s"] = run.trace_result["window_s"]
        result["breakdown"] = {
            "device_ops": run.trace_result["device_ops"],
            "idle_gaps": run.trace_result["idle_gaps"]}
    metrics = {}
    for name, reader in cell.metric_readers(trace).items():
        value = reader.read(run)
        if value is not None:
            metrics[name] = {"value": value,
                             "unit": cell.metric_spec(name)["unit"]}
    spans = sorted(b - a for a, b in run.spans.intervals["bench.execute"]
                   if run.t0 <= a < run.t1)
    earlier = {"workload": workload, "seed": run.seed,
               "window_s": run.t1 - run.t0, "setup_s": run.setup_s,
               "compiles_in_window": run.compiles.count(run.t0, run.t1),
               "execute_s": {"min": spans[0], "median": spans[len(spans) // 2],
                             "max": spans[-1]} if spans else None,
               **run.counters}
    if trace:
        earlier["trace"] = devtrace.describe(run.trace_obj)
    run.trace_obj = None

    correct, checks = driver.check(system, run)
    del system
    gc.collect()
    attempted = [it for it in run.items if it.start is not None]
    result = {"correct": bool(correct), "attempted": len(attempted),
              "failed": sum(1 for it in attempted if not it.ok),
              "metrics": metrics, "device": device, **result,
              "checks": checks}
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=log)
    return {"earlier": earlier, "result": result}


def judge(numbers: Dict[str, float], limits: Dict[str, float]):
    """``(correct, checks)``: every number at or under its limit. A number
    that could not be computed (NaN) fails."""
    checks = {k: {"value": float(v), "limit": float(limits[k])}
              for k, v in numbers.items()}
    ok = all(not math.isnan(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks


def dumps(obj) -> str:
    return json.dumps(obj, allow_nan=True)
