"""S2CE core: cost model, placement (vs exhaustive oracle), offload
hysteresis, SLA tracking, end-to-end orchestrator run."""

import numpy as np
import pytest

from repro.core import costmodel as cm
from repro.core.offload import OffloadController
from repro.core.orchestrator import Orchestrator, StreamJob
from repro.core.placement import (Objective, place_frontier,
                                  place_graph_exhaustive, standard_pipeline)
from repro.core.sla import SLA, SLATracker
from repro.streams.generators import DriftSpec, HyperplaneStream

RES = {"edge": cm.EDGE_NODE, "cloud": cm.CLOUD_POD}


def test_stage_time_roofline_max():
    op = cm.OperatorCost("x", flops_per_event=1e6, bytes_per_event=1e3,
                         out_bytes_per_event=10)
    t = cm.stage_time(op, cm.EDGE_NODE, rate=1e3)
    assert t == pytest.approx(max(1e9 / 2e12, 1e6 / 50e9))


@pytest.mark.parametrize("rate", [1e2, 1e4, 1e6])
def test_prefix_cut_matches_exhaustive_oracle(rate):
    pipe = standard_pipeline(dim=16)
    obj = Objective()
    best, frontier = place_frontier(pipe, RES, rate, obj)
    oracle = place_graph_exhaustive(pipe, RES, rate, obj)
    assert frontier == frozenset(pipe.names[:len(frontier)])
    assert obj.score(best) <= obj.score(oracle) * 1.0001, (
        "placement of a linear pipeline must match the exhaustive oracle")


def test_dl_train_never_on_edge():
    pipe = standard_pipeline(dim=16)
    for rate in (1e2, 1e5):
        plan, _ = place_frontier(pipe, RES, rate)
        assert plan.assignment["dl_train"] == "cloud"


def test_high_rate_pushes_work_to_cloud():
    pipe = standard_pipeline(dim=64)
    _, edge_lo = place_frontier(pipe, RES, 1e3)
    _, edge_hi = place_frontier(pipe, RES, 5e6)
    assert edge_hi <= edge_lo, "rising rate must move stages off the edge"


def test_offload_hysteresis_no_thrash():
    ctl = OffloadController(standard_pipeline(dim=32), RES, cooldown=3)
    ctl.initial_plan(1e4)
    # oscillate +-10% (inside the 1.3x band): no migrations
    for step in range(1, 30):
        rate = 1e4 * (1.1 if step % 2 else 0.9)
        ctl.observe(step, rate)
    assert ctl.migrations() == 0


def test_offload_reacts_to_burst():
    ctl = OffloadController(standard_pipeline(dim=64), RES, cooldown=1)
    d0 = ctl.initial_plan(1e3)
    d1 = ctl.observe(1, 1e7)       # big burst
    assert d1.cut <= d0.cut
    assert d1.reason == "rate_up"


def test_sla_tracker_p99_and_violations():
    t = SLATracker(SLA(max_latency_s=0.1))
    for i in range(100):
        t.observe(0.01 if i % 10 else 0.5, 1e4)
    assert t.violation_rate == pytest.approx(0.1)
    assert t.p99_latency >= 0.1
    assert not t.ok()


def test_orchestrator_end_to_end_adapts_to_drift():
    job = StreamJob("e2e", dim=8, drift_detector="ddm", sample_rate=0.8)
    orch = Orchestrator(job)
    gen = HyperplaneStream(dim=8, seed=0,
                           drift=DriftSpec("abrupt", at=0.5),
                           horizon=64 * 60.0)
    batches = [gen.batch(i, 64) for i in range(60)]
    m = orch.run(batches)
    assert m.events == 60 * 64
    assert m.drift_alarms >= 1, "DDM should fire on the abrupt concept flip"
    assert m.preq["accuracy"] > 0.6
    assert m.preq["ewma_accuracy"] > 0.65, (
        "post-drift recovery (soft reset) should restore accuracy")
