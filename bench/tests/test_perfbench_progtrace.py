"""Metrics that read the program's ``s2ce.*`` spans and named programs:
idle time counted only inside ``s2ce.execute_batch``, device time of one
program per traced item, the median span per decode step, idle by
innermost span, and nothing (no error) from a trace the program put no
spans in. Checked on hand-made traces and on the recorded v5e fixture."""

import dataclasses
import pathlib
import shutil

import pytest

import _paths  # noqa: F401
from bench import cells, devtrace, harness, progtrace, readers

FIXTURES = pathlib.Path(__file__).resolve().parent / "fixtures"
DEV = "/device:TPU:0"


def _made():
    tr = devtrace.Trace()
    tr.ops[DEV] = [(1.0, 2.0, "fusion.1", "jit_sample"),
                   (2.0, 2.5, "while.8", "jit_sample"),
                   (4.0, 5.0, "while", "jit_drift"),
                   (9.0, 12.0, "late", "jit_sample")]
    tr.modules[DEV] = [(1.0, 2.5, "jit_sample", "jit_sample"),
                       (4.0, 5.0, "jit_drift", "jit_drift"),
                       (9.0, 12.0, "jit_sample", "jit_sample")]
    tr.host = [
        (0.0, 0.8, "s2ce.execute_batch", ""),       # starts before t0
        (0.8, 3.0, "s2ce.execute_batch", ""),
        (0.9, 1.0, "s2ce.stage_batch", ""),
        (2.7, 3.0, "s2ce.drift_check", ""),
        (3.0, 3.5, "s2ce.control.observe", ""),
        (3.5, 5.5, "s2ce.execute_batch", ""),
        (5.5, 5.501, "s2ce.serve.decode_step", ""),
        (5.6, 5.603, "s2ce.serve.decode_step", ""),
        (12.0, 12.5, "s2ce.serve.decode_step", ""),  # after the window
    ]
    return tr


T0, T1 = 0.5, 10.0


def _run(monkeypatch, tr, items=2, workload="fanout_moa.saturated"):
    run = harness.Run(cell=cells.load_cell(workload), seed=1, seconds=1.0,
                      trace=True, t_process=0.0)
    run.trace_result = {"t0": T0, "t1": T1}
    run.traced["items"] = [object()] * items
    monkeypatch.setattr(progtrace, "load", lambda r: tr)
    return run


def test_dispatch_idle_counts_only_idle_inside_execute_batch(monkeypatch):
    run = _run(monkeypatch, _made())
    # inside execute_batch: [0.5, 1] + [2.5, 3] + [3.5, 4] + [5, 5.5];
    # the control span's idle [3, 3.5] and the rest of the window are out
    want = 100 * (0.5 + 0.5 + 0.5 + 0.5) / (T1 - T0)
    assert progtrace.dispatch_idle_pct(run) == pytest.approx(want)
    # the device's idle over the whole window is at least as much
    busy = devtrace.busy_s(_made(), T0, T1)
    assert progtrace.dispatch_idle_pct(run) <= 100 * (
        1 - busy / (T1 - T0))


def test_dispatch_idle_averages_over_devices(monkeypatch):
    tr = _made()
    tr.ops["/device:TPU:1"] = [(0.5, 10.0, "all", "jit_sample")]
    run = _run(monkeypatch, tr)
    assert progtrace.dispatch_idle_pct(run) == pytest.approx(
        100 * 2.0 / 2 / (T1 - T0))


@pytest.mark.parametrize("items,want_ms", [(1, 2500.0), (2, 1250.0),
                                           (5, 500.0)])
def test_program_time_is_per_traced_item(monkeypatch, items, want_ms):
    run = _run(monkeypatch, _made(), items=items)
    # jit_sample: [1, 2.5] and [9, 10] of [9, 12] inside the window
    assert progtrace.program_ms_per_item(run, "jit_sample") == \
        pytest.approx(want_ms)
    assert progtrace.program_ms_per_item(run, "jit_fn") is None


def test_span_mean_is_per_step_in_the_window(monkeypatch):
    tr = _made()
    run = _run(monkeypatch, tr, workload="qwen2_1_5b.serve_saturated")
    # two steps in the window, 1 and 3 ms: the median of an even count
    # is the mean of the middle two; the step after the window is out
    assert progtrace.span_median_ms(run, progtrace.DECODE_SPAN) == \
        pytest.approx(2.0)
    # steps that wait out a prefill on the device move the median little
    tr.host += [(0.6, 0.7, progtrace.DECODE_SPAN, ""),
                (5.7, 5.702, progtrace.DECODE_SPAN, ""),
                (5.8, 5.802, progtrace.DECODE_SPAN, "")]
    assert progtrace.span_median_ms(run, progtrace.DECODE_SPAN) == \
        pytest.approx(2.0)
    tr.host.append((5.9, 5.9025, progtrace.DECODE_SPAN, ""))
    assert progtrace.span_median_ms(run, progtrace.DECODE_SPAN) == \
        pytest.approx(2.25)
    assert progtrace.span_median_ms(run, "s2ce.serve.prefill") is None


def test_idle_by_innermost_span():
    rows = dict(map(tuple, progtrace.idle_by_innermost(_made(), T0, T1)))
    # idle [0.5, 1], [2.5, 4] and [5, 9], each piece under the span that
    # opened last of those open over it
    assert rows == pytest.approx({
        "s2ce.execute_batch": 0.3 + 0.1 + 0.2 + 0.5 + 0.5,
        "s2ce.stage_batch": 0.1, "s2ce.drift_check": 0.3,
        "s2ce.control.observe": 0.5, "s2ce.serve.decode_step": 0.004,
        progtrace.UNCOVERED: 0.099 + 3.397})
    busy = devtrace.busy_s(_made(), T0, T1)
    assert sum(rows.values()) == pytest.approx(T1 - T0 - busy)


def test_the_four_metric_files_read_their_cells(monkeypatch):
    tr = _made()
    for workload, want in (
            ("fanout_moa.saturated", {"sample_op_ms_per_batch",
                                      "dispatch_idle.fanout_saturated"}),
            ("qwen2_1_5b.serve_saturated",
             {"dispatch_idle.serve_saturated",
              "decode_step_host_ms"})):
        run = _run(monkeypatch, tr, workload=workload)
        got = {name: reader.read(run)
               for name, reader in run.cell.metric_readers(True).items()
               if name in want}
        assert set(got) == want
        assert all(v is not None and v > 0 for v in got.values()), got


def test_a_trace_without_program_spans_reads_none(tmp_path, monkeypatch):
    """The recorded fixture has the benchmark's spans and none of the
    program's, as a program before these spans gives."""
    cell = cells.load_cell("fanout_moa.saturated")
    cell = dataclasses.replace(cell, bench_dir=tmp_path / "bench")
    tdir = harness.trace_dir(tmp_path, cell.name)
    tdir.mkdir(parents=True)
    shutil.copy(FIXTURES / "v5e_small.xplane.pb", tdir / "t.xplane.pb")
    run = harness.Run(cell=cell, seed=1, seconds=1.0, trace=True,
                      t_process=0.0)
    run.trace_obj = devtrace.read(devtrace.find_xplane(str(tdir)))
    run.trace_result = devtrace.reduce(run.trace_obj)
    run.traced["items"] = [object()] * 3
    tr = progtrace.load(run)
    assert tr is progtrace.load(run)            # parsed once per run
    assert tr.ops and not tr.host
    assert progtrace.dispatch_idle_pct(run) is None
    assert progtrace.span_median_ms(run, progtrace.DECODE_SPAN) is None
    assert progtrace.program_ms_per_item(run, "jit_sample") is None
    assert readers.idle_pct(run) > 0
    run.trace_result = None
    assert progtrace.load(run) is None
