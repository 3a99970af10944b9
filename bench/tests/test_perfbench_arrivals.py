"""The traffic generator: schedules are a function of the seed, and draws
follow the traffic file's weights."""

import collections

import numpy as np
import pytest

import _paths  # noqa: F401
from bench import arrivals, cells

REQUESTS = {"kind": "requests", "rate": 50.0, "block": 30,
            "size_keys": ["prompt_len", "output_len"],
            "classes": [{"prompt_len": p, "output_len": o, "weight": w}
                        for p, o, w in ((128, 64, 4), (256, 16, 3),
                                        (512, 32, 2), (1024, 16, 1))]}
STREAM = {"kind": "stream", "rate": 40000.0, "batch_events": 4096}


@pytest.mark.parametrize("traffic", [REQUESTS, STREAM])
def test_schedule_is_a_function_of_the_seed(traffic):
    a = arrivals.schedule(traffic, 2**31 + 17, 20.0)
    b = arrivals.schedule(traffic, 2**31 + 17, 20.0)
    c = arrivals.schedule(traffic, 5, 20.0)
    assert a == b
    assert [x.due for x in a] != [x.due for x in c]
    assert all(0 <= x.due < 20.0 for x in a)
    assert all(p.due < q.due for p, q in zip(a, a[1:]))


@pytest.mark.parametrize("traffic", [REQUESTS, STREAM])
def test_offered_rate_matches_the_file(traffic):
    items = arrivals.schedule(traffic, 3, 200.0)
    per = traffic.get("batch_events", 1)
    assert len(items) * per / 200.0 == pytest.approx(traffic["rate"],
                                                     rel=0.05)


def test_draws_follow_the_weights_block_by_block():
    items = arrivals.schedule(REQUESTS, 11, 60.0)
    n = len(items) // 30 * 30
    want = {(c["prompt_len"], c["output_len"]): c["weight"] * 3
            for c in REQUESTS["classes"]}
    for b in range(0, n, 30):
        got = collections.Counter((it.sizes["prompt_len"],
                                   it.sizes["output_len"])
                                  for it in items[b:b + 30])
        assert got == want


def test_every_seed_offers_the_same_work_in_another_order():
    a = arrivals.schedule(REQUESTS, 1, 120.0)[:300]
    b = arrivals.schedule(REQUESTS, 2, 120.0)[:300]
    assert sorted(x.sizes["prompt_len"] for x in a) == \
        sorted(x.sizes["prompt_len"] for x in b)
    gaps = lambda s: sorted(np.round(np.diff([0.0] + [x.due for x in s]),
                                     9))
    assert gaps(a) == gaps(b)
    assert [x.sizes for x in a] != [x.sizes for x in b]


def test_weights_that_do_not_fill_a_block_are_refused():
    bad = dict(REQUESTS, block=7)
    with pytest.raises(ValueError):
        arrivals.schedule(bad, 1, 5.0)


@pytest.mark.parametrize("name", ["stream_backlog_b16384",
                                  "azure_2023_backlog", "azure_2023_poisson"])
def test_committed_traffic_files_generate(name):
    traffic = cells.load_json(cells.BENCH_DIR / "traffic" / f"{name}.json")
    items = arrivals.schedule(traffic, 123456789012, 5.0)
    assert items and items[0].due >= 0


def test_a_saturated_wave_of_the_azure_mix_is_always_the_same():
    """Block and wave are both 16, so every full wave holds eight requests
    of each service, each class whole: seeds change the order, not the
    work."""
    traffic = cells.load_json(cells.BENCH_DIR / "traffic"
                              / "azure_2023_backlog.json")
    for seed in (1, 2**33 + 7):
        items = arrivals.schedule(traffic, seed, 2.0)
        assert len(items) >= 64
        for b in range(0, len(items) // 16 * 16, 16):
            got = collections.Counter(
                (it.sizes["prompt_len"], it.sizes["output_len"])
                for it in items[b:b + 16])
            assert got == {(1020, 129): 8, (1500, 13): 8}
    assert arrivals.size_values(traffic, "prompt_len") == [1020, 1500]


def test_an_open_backlog_is_due_as_the_window_opens():
    traffic = dict(REQUESTS, open_backlog=16)
    items = arrivals.schedule(traffic, 8, 10.0)
    assert [x.due for x in items[:16]] == [0.0] * 16
    assert items[16].due > 0.0
    assert [x.sizes for x in items] == [
        x.sizes for x in arrivals.schedule(REQUESTS, 8, 10.0)]
