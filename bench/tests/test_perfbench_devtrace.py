"""The trace reduction: busy time from the union of device op intervals,
time per program, the top ops, and idle gaps labelled by the host span
that covers them. Checked on a hand-made trace and on a small trace
recorded on a TPU v5e (``record_trace_fixture.py``)."""

import json
import pathlib

import pytest

import _paths  # noqa: F401
from bench import devtrace

FIXTURES = pathlib.Path(__file__).resolve().parent / "fixtures"


def _made():
    tr = devtrace.Trace()
    tr.ops["/device:TPU:0"] = [
        (1.0, 2.0, "fusion.1", "jit_a"), (1.5, 2.5, "fusion.2", "jit_a"),
        (4.0, 5.0, "custom-call.3", "jit_b"), (9.0, 12.0, "late", "jit_c")]
    tr.modules["/device:TPU:0"] = [(1.0, 2.5, "jit_a(1)", "jit_a(1)"),
                                   (4.0, 5.0, "jit_b(2)", "jit_b(2)")]
    tr.host = [(0.5, 10.0, devtrace.WINDOW_SPAN, ""),
               (2.5, 4.0, "bench.control", ""),
               (5.0, 9.5, "bench.wait_arrival", "")]
    return tr


def test_busy_is_the_union_inside_the_window():
    red = devtrace.reduce(_made())
    assert red["window_s"] == pytest.approx(9.5)
    # [1, 2.5] + [4, 5] + [9, 10] clipped to the window
    assert red["busy_s"] == pytest.approx(3.5)


def test_idle_gaps_are_labelled_by_the_covering_span():
    gaps = dict(map(tuple, devtrace.reduce(_made())["idle_gaps"]))
    assert gaps["bench.wait_arrival"] == pytest.approx(4.0)
    assert gaps["bench.control"] == pytest.approx(1.5)
    assert gaps["host.other"] == pytest.approx(0.5)
    assert sum(gaps.values()) == pytest.approx(9.5 - 3.5)


def test_time_per_program_and_top_ops():
    tr = _made()
    secs, n = devtrace.time_where(tr, 0.5, 10.0,
                                  lambda name, mod: mod == "jit_a")
    assert (secs, n) == (pytest.approx(1.5), 2)
    secs, n = devtrace.time_where(tr, 0.5, 10.0,
                                  lambda name, mod: name.startswith("jit_b"),
                                  modules=True)
    assert (secs, n) == (pytest.approx(1.0), 1)
    top = devtrace.reduce(tr)["device_ops"]
    assert top[0][0] == "jit_a:fusion.1" or top[0][1] >= top[1][1]


def test_a_trace_without_its_window_span_is_refused():
    tr = _made()
    tr.host = tr.host[1:]
    with pytest.raises(ValueError):
        devtrace.reduce(tr)


def test_recorded_v5e_trace():
    meta = json.loads((FIXTURES / "v5e_small.json").read_text())
    tr = devtrace.read(str(FIXTURES / "v5e_small.xplane.pb"))
    red = devtrace.reduce(tr)
    assert red["devices"] == 1
    assert 0 < red["busy_s"] < red["window_s"]
    gaps = dict(map(tuple, red["idle_gaps"]))
    # the three sleeps are idle device time under bench.wait_arrival
    assert gaps["bench.wait_arrival"] >= meta["runs"] * meta["sleep_s"] * 0.9
    secs, n = devtrace.time_where(
        tr, red["t0"], red["t1"], lambda name, mod: "lambda" in name,
        modules=True)
    # the three program runs hold all the busy time (a program's span
    # also covers its launch, a few nanoseconds more than its ops)
    assert n == meta["runs"]
    assert red["busy_s"] <= secs <= red["busy_s"] * 1.01
