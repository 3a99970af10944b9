"""End-to-end metrics over the whole window: a stall anywhere in it moves
both the rate and the tail."""

import pytest

import _paths  # noqa: F401
from bench import cells, harness, readers


def _run(stall_at=None, stall=0.0, n=200, service=0.05, gap=0.06):
    cell = cells.load_cell("fanout_moa.saturated")
    run = harness.Run(cell=cell, seed=1, seconds=n * gap, trace=False,
                      t_process=0.0)
    run.t0 = 100.0
    free = run.t0
    for i in range(n):
        due = run.t0 + (i + 1) * gap
        start = max(due, free)
        if i == stall_at:
            start += stall
        done = start + service
        free = done
        run.items.append(harness.Item(i, due, {"events": 4096}, start, done,
                                      True))
    run.close_window()
    return run


def test_rate_and_tail_move_with_a_stall():
    calm = _run()
    stalled = _run(stall_at=100, stall=3.0)
    assert readers.rate(stalled, "events") < readers.rate(calm, "events")
    assert readers.latency_p95(stalled) > readers.latency_p95(calm) + 0.5


def test_tail_counts_items_never_done_as_late():
    run = _run()
    for it in run.items[-20:]:
        it.ok, it.done = False, None
    assert readers.latency_p95(run) == float("inf")


def test_rate_is_over_all_the_work_and_all_the_time():
    run = _run(n=100)
    window = run.t1 - run.t0
    assert readers.rate(run, "events") == pytest.approx(100 * 4096 / window)


@pytest.mark.parametrize("q,want", [(0.95, 95), (0.5, 50), (1.0, 100)])
def test_percentile_is_nearest_rank(q, want):
    assert harness.percentile(list(range(1, 101)), q) == want
