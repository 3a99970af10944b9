"""Rate-adaptive uplink codec control: windowed SLA telemetry (the
`SLATracker.window` bugfix), rate-aware codec re-admission at replan
time, the (frontier x pool x codec) plan search, codec-migration
hysteresis, EF-residual flush at the swap, and executed-migration
counting on the full (assignment, codec) plan identity."""

import numpy as np
import pytest

from repro.core import codecs as cd
from repro.core import costmodel as cm
from repro.core import pipeline as pl
from repro.core.offload import OffloadController
from repro.core.orchestrator import Orchestrator, StreamJob
from repro.core.placement import Objective, place_frontier
from repro.core.sla import (SLA, UPLINK_RELAXED, UPLINK_SATURATED,
                            SLATracker, codec_candidates, pick_codec)
from repro.streams.generators import HyperplaneStream

LOOSE = SLA(max_latency_s=1e3, error_budget=11.0)   # only rate drives replans


def _pipe(dim=8):
    return pl.standard_stream_pipeline(dim=dim, sample_rate=0.5)


def _batches(n, dim=8, n_per=32, seed=0):
    gen = HyperplaneStream(dim=dim, seed=seed, horizon=n * n_per)
    return [gen.batch(i, n_per) for i in range(n)]


# ---------------------------------------------------------------------------
# satellite bugfix: SLATracker honors `window`, violations age out
# ---------------------------------------------------------------------------

def test_sla_tracker_recovers_after_clean_stretch():
    """Regression: `window` used to be ignored (deques hardcoded
    maxlen=1000, violations/checks were lifetime counters), so ok()
    could never recover after an early violation burst."""
    t = SLATracker(SLA(max_latency_s=0.1), window=20)
    for _ in range(10):
        t.observe(0.5, 1e4)              # violation burst
    assert not t.ok()
    assert t.violation_rate == pytest.approx(1.0)
    for _ in range(20):                  # a full window of clean behavior
        t.observe(0.01, 1e4)
    assert t.violation_rate == 0.0
    assert t.ok(), "violations must age out of the window"
    # lifetime counters remain for audit
    assert t.violations == 10 and t.checks == 30


def test_sla_tracker_deques_honor_window():
    t = SLATracker(SLA(), window=5)
    for i in range(50):
        t.observe(0.01 * i, 100.0 + i)
    assert len(t.latencies) == 5 and len(t.throughputs) == 5
    assert list(t.throughputs) == [145.0, 146.0, 147.0, 148.0, 149.0]
    assert t.window_checks == 5


def test_sla_tracker_partial_window_rates():
    t = SLATracker(SLA(max_latency_s=0.1, min_throughput=50.0), window=100)
    t.observe(0.5, 100.0)                # latency violation only
    t.observe(0.01, 10.0)                # throughput violation only
    t.observe(0.01, 100.0)               # clean
    assert t.violation_rate == pytest.approx(2 / 3)
    assert t.latency_violation_rate == pytest.approx(1 / 3)
    assert t.throughput_violation_rate == pytest.approx(1 / 3)
    r = t.report()
    assert r["violation_rate"] == pytest.approx(2 / 3)
    assert r["window_checks"] == 3.0


def test_sla_tracker_rejects_nonpositive_window():
    with pytest.raises(ValueError, match="window"):
        SLATracker(SLA(), window=0)


def test_interleaved_trackers_recover_independently():
    """Multi-tenant telemetry: two trackers with DIFFERENT windows fed
    from one shared clock (the fleet's round-robin interleave) must keep
    fully independent state — a shared saturation burst ages out of each
    tracker at its own window, and one tenant's recovery never reads the
    other's history."""
    sla = SLA(max_latency_s=0.1)
    short = SLATracker(sla, window=10)
    long = SLATracker(sla, window=40)
    # shared clean warmup, then a shared 12-step saturation burst — the
    # same (latency, throughput) sample goes to both, as when one
    # congested uplink slows every tenant's round
    for _ in range(20):
        for t in (short, long):
            t.observe(0.01, 1e4)
    for _ in range(12):
        for t in (short, long):
            t.observe(0.5, 1e4)
    assert not short.ok() and not long.ok()
    assert short.violation_rate == pytest.approx(1.0)      # window=10 < burst
    # long window not yet full: 32 samples observed, 12 violating
    assert long.violation_rate == pytest.approx(12 / 32)
    # 10 clean interleaved rounds: the short window is fully clean and
    # recovers; the long window still carries the burst
    for _ in range(10):
        for t in (short, long):
            t.observe(0.01, 1e4)
    assert short.ok() and short.violation_rate == 0.0
    assert not long.ok()
    assert long.violation_rate == pytest.approx(12 / 40)
    # after enough rounds the long window ages the burst out too
    for _ in range(30):
        long.observe(0.01, 1e4)
    assert long.ok() and long.violation_rate == 0.0
    # the recovered short tracker was untouched by long's extra steps
    assert short.window_checks == 10 and short.violation_rate == 0.0
    # lifetime audit counters stay per-tenant
    assert short.violations == 12 and long.violations == 12
    assert short.checks == 42 and long.checks == 72


# ---------------------------------------------------------------------------
# satellite bugfix: observe() before initial_plan()
# ---------------------------------------------------------------------------

def test_observe_before_initial_plan_takes_initial_lazily():
    """Regression: observe() before initial_plan() raised IndexError on
    history[-1]; it now takes the initial plan lazily."""
    g = pl.fanout_stream_graph(dim=16)
    ctl = OffloadController(g, cm.ClusterSpec.edge_cloud())
    d = ctl.observe(step=7, rate=1e4)
    assert d.reason == "initial"
    assert d.step == 7
    assert ctl.history and ctl.migrations() == 0
    # and the controller proceeds normally afterwards
    d2 = ctl.observe(step=8, rate=1e4)
    assert d2.reason == "hold"


# ---------------------------------------------------------------------------
# rate-aware admission policy (sla.codec_candidates / pick_codec)
# ---------------------------------------------------------------------------

def test_pick_codec_without_report_is_static_admission():
    assert pick_codec(SLA(error_budget=11.0)).name == "topk_int8_ef"
    assert pick_codec(SLA(error_budget=0.0)).name == "identity"


def test_saturated_report_admits_full_escalation_ladder():
    names = [c.name for c in codec_candidates(
        SLA(error_budget=11.0), report={"uplink_utilization": 0.95,
                                        "violation_rate": 0.0})]
    assert names == ["identity", "int8_ef", "topk_ef", "topk_int8_ef"]
    # and the single-codec pick escalates to the cheapest wire
    c = pick_codec(SLA(error_budget=11.0),
                   report={"uplink_utilization": 1.5, "violation_rate": 0.0})
    assert c.name == "topk_int8_ef"


def test_relaxed_link_deescalates_to_lossless():
    c = pick_codec(SLA(error_budget=11.0),
                   report={"uplink_utilization": 0.1, "violation_rate": 0.0,
                           "codec": "topk_int8_ef"})
    assert c.name == "identity"


def test_nonbandwidth_violations_deescalate_even_in_dead_band():
    """Latency violations with an unsaturated link come from compute/
    staleness, not bandwidth — compression is not buying anything, go
    lossless. (A bare report without per-cause rates falls back to the
    aggregate violation_rate.)"""
    c = pick_codec(SLA(error_budget=11.0),
                   report={"uplink_utilization": 0.7, "violation_rate": 0.2,
                           "latency_violation_rate": 0.2,
                           "codec": "topk_int8_ef"})
    assert c.name == "identity"
    bare = pick_codec(SLA(error_budget=11.0),
                      report={"uplink_utilization": 0.7,
                              "violation_rate": 0.2,
                              "codec": "topk_int8_ef"})
    assert bare.name == "identity"


def test_throughput_violations_do_not_force_lossless():
    """Regression: throughput violations are bandwidth symptoms — in the
    dead band they must KEEP the incumbent lossy codec (de-escalating
    would starve the wire harder), not de-escalate to lossless."""
    cands = codec_candidates(
        SLA(error_budget=11.0),
        report={"uplink_utilization": 0.7, "violation_rate": 0.2,
                "latency_violation_rate": 0.0,
                "throughput_violation_rate": 0.2,
                "codec": "topk_int8_ef"})
    assert [c.name for c in cands] == ["topk_int8_ef"]


def test_dead_band_keeps_the_incumbent_codec():
    mid = (UPLINK_RELAXED + UPLINK_SATURATED) / 2
    for inc in ("int8_ef", "topk_ef"):
        cands = codec_candidates(
            SLA(error_budget=11.0),
            report={"uplink_utilization": mid, "violation_rate": 0.0,
                    "codec": inc})
        assert [c.name for c in cands] == [inc]


def test_rate_aware_admission_never_exceeds_budget():
    """Acceptance invariant: telemetry can narrow the candidate set but
    never admit past the error budget."""
    reports = [None,
               {"uplink_utilization": 5.0, "violation_rate": 0.0},
               {"uplink_utilization": 0.0, "violation_rate": 1.0},
               {"uplink_utilization": 0.7, "violation_rate": 0.0,
                "codec": "topk_int8_ef"}]
    for budget in np.linspace(0.0, 12.0, 25):
        sla = SLA(error_budget=float(budget))
        for rep in reports:
            for c in codec_candidates(sla, report=rep):
                assert c.error_bound <= budget + 1e-12, (budget, rep, c.name)
            assert pick_codec(sla, report=rep).error_bound <= budget + 1e-12


# ---------------------------------------------------------------------------
# the codec as a searched plan dimension (placement)
# ---------------------------------------------------------------------------

def test_codec_search_restores_feasibility_under_saturation():
    """At a rate where every identity-codec plan over-runs the uplink,
    the (frontier x pool x codec) search must find a feasible lossy
    plan and record the codec it was priced under."""
    g = _pipe(dim=8)
    spec = cm.ClusterSpec.edge_cloud()
    rate = 8e7
    ident, _ = place_frontier(g, spec, rate, codecs=["identity"])
    assert not ident.feasible, "ramp rate must saturate the identity uplink"
    plan, frontier = place_frontier(
        g, spec, rate, codecs=["identity", "int8_ef", "topk_int8_ef"])
    assert plan.feasible
    assert plan.uplink_codec in ("int8_ef", "topk_int8_ef")
    assert plan.uplink_utilization < 1.0


def test_codec_search_ties_resolve_toward_first_candidate():
    """With no uplink pressure the scores differ only by the tiny
    uplink term; candidates are passed most-faithful-first so a lossy
    codec must EARN its place via the score, and identity-only search
    stays identical to the historical behavior."""
    g = _pipe(dim=8)
    spec = cm.ClusterSpec.edge_cloud()
    plan, _ = place_frontier(g, spec, 1e3, codecs=["identity"])
    assert plan.uplink_codec == "identity"
    base, _ = place_frontier(g, spec, 1e3)
    assert base.uplink_codec is None
    assert base.assignment == plan.assignment
    assert base.latency_s == pytest.approx(plan.latency_s)


# ---------------------------------------------------------------------------
# controller: codec escalation/de-escalation with hysteresis
# ---------------------------------------------------------------------------

def _ramp_controller(**kw):
    g = _pipe(dim=8)
    return OffloadController(g, cm.ClusterSpec.edge_cloud(),
                             codec="topk_int8_ef", sla_spec=LOOSE, **kw)


def test_controller_deescalates_and_reescalates_once_each():
    rates = [8e7] * 10 + [1e4] * 10 + [8e7] * 10
    ctl = _ramp_controller()
    ctl.initial_plan(rates[0])
    for step, r in enumerate(rates):
        ctl.observe(step, r)
    codecs = [d.codec for d in ctl.history]
    changes = [(a, b) for a, b in zip(codecs, codecs[1:]) if a != b]
    assert changes == [("topk_int8_ef", "identity"),
                       ("identity", "topk_int8_ef")], codecs


def test_codec_cooldown_blocks_flapping():
    """Within codec_cooldown decisions of a swap, replans keep the
    incumbent codec even when admission would change it."""
    rates = [8e7] * 3 + [1e4] * 3 + [8e7] * 3 + [1e4] * 3
    ctl = _ramp_controller(cooldown=1, codec_cooldown=100)
    ctl.initial_plan(rates[0])
    for step, r in enumerate(rates):
        ctl.observe(step, r)
    codecs = {d.codec for d in ctl.history}
    assert codecs == {"topk_int8_ef"}, (
        "codec_cooldown must pin the codec through the oscillation")


def test_codec_change_is_a_plan_identity_change():
    """Plan identity keys on (assignment, codec): a codec-only swap
    counts as a migration even when the frontier never moves."""
    ctl = _ramp_controller(cooldown=1, codec_cooldown=1)
    ctl.initial_plan(1e4)        # low rate, lossy incumbent
    d = ctl.observe(1, 3e4)      # out of band -> replan -> de-escalate
    assert d.codec == "identity"
    assert d.frontier == ctl.history[0].frontier
    assert ctl.migrations() == 1


def test_fixed_codec_controller_unchanged_without_sla_spec():
    """No sla_spec -> the historical fixed-codec behavior: the codec is
    pinned no matter what the rate does."""
    g = _pipe(dim=8)
    ctl = OffloadController(g, cm.ClusterSpec.edge_cloud(),
                            codec="int8_ef", cooldown=1)
    assert not ctl._adaptive
    ctl.initial_plan(1e4)
    for step, r in enumerate([8e7, 1e3, 8e7, 1e3], start=1):
        d = ctl.observe(step, r)
        assert d.codec == "int8_ef"


def test_user_declared_link_codec_survives_adaptive_replans():
    """A per-link codec the user declared is pinned: the blanket
    candidate fills only undeclared uplinks (with_uplink_codec default),
    so adaptive control cannot override an explicit topology choice."""
    spec = cm.ClusterSpec(
        pools=[cm.EDGE_NODE, cm.CLOUD_POD],
        links=[cm.Link("edge", "cloud", bw=1e9, latency=20e-3,
                       codec="int8_ef")])
    g = _pipe(dim=8)
    ctl = OffloadController(g, spec, codec="int8_ef",
                            sla_spec=SLA(max_latency_s=1e3, error_budget=0.1),
                            cooldown=1, codec_cooldown=1)
    ctl.initial_plan(1e4)
    d = ctl.observe(1, 8e7)
    # the declared link keeps int8_ef regardless of the blanket pick
    spec2 = ctl.resources.with_uplink_codec(d.codec)
    assert spec2.link("edge", "cloud").codec == "int8_ef"


# ---------------------------------------------------------------------------
# orchestrator: live codec migration, EF-residual flush, executed
# migration counting on full plan identity
# ---------------------------------------------------------------------------

def test_swap_codec_flushes_stale_residuals():
    orch = Orchestrator(StreamJob("swap", dim=8, sla=LOOSE))
    assert orch.codec.name == "topk_int8_ef"
    orch._uplink_residuals["x"] = np.ones((4, 8), np.float32)
    orch._swap_codec("int8_ef", step=3)
    assert orch.codec.name == "int8_ef"
    assert orch._uplink_residuals == {}, (
        "a stale residual from the old codec must not leak into the new")
    assert any(d == "3:codec topk_int8_ef->int8_ef"
               for d in orch.metrics.decisions)
    # swapping to identity tears the wire transform down entirely
    orch._uplink_residuals["x"] = np.ones((4, 8), np.float32)
    orch._swap_codec("identity", step=9)
    assert orch._uplink_residuals == {} and orch._uplink is None


def test_orchestrated_ramp_escalates_codec_once_each_way():
    """The satellite system test: a saturating rate ramp drives a live
    codec escalation and back at migration boundaries — no restart,
    exactly one codec migration each way, never over budget."""
    rates = [8e7] * 10 + [1e4] * 10 + [8e7] * 10
    job = StreamJob("ramp", dim=8, sla=LOOSE)
    orch = Orchestrator(job)
    m = orch.run(_batches(30), rate_fn=lambda s: rates[min(s, len(rates) - 1)])
    assert m.codec == "topk_int8_ef"          # the initial admission pick
    changes = [(a, b) for a, b in zip(m.codecs, m.codecs[1:]) if a != b]
    assert changes == [("topk_int8_ef", "identity"),
                       ("identity", "topk_int8_ef")], m.codecs
    # codec migrations land at replan boundaries, visible in decisions
    assert sum(1 for d in m.decisions if ":codec " in d) == 2
    # never admits over budget (acceptance)
    for name in set(m.codecs):
        assert cd.get_codec(name).error_bound <= job.sla.error_budget + 1e-12
    # the run ends lossy: residuals are live again after the last swap
    assert orch._uplink_residuals


def test_orchestrated_ramp_ending_lossless_leaves_no_residuals():
    """After the de-escalation swap the EF residuals are flushed and the
    identity codec never reseeds them — stale carry cannot survive a
    codec migration."""
    rates = [8e7] * 10 + [1e4] * 10
    orch = Orchestrator(StreamJob("down", dim=8, sla=LOOSE))
    m = orch.run(_batches(20), rate_fn=lambda s: rates[min(s, len(rates) - 1)])
    assert m.codecs[-1] == "identity"
    assert "identity" not in m.codecs[:5]      # it did start lossy
    assert orch._uplink_residuals == {}


def test_executed_migrations_count_codec_only_changes():
    """Satellite: executed-migration counting keys on the full
    (assignment, codec) identity, not the frontier view — a codec swap
    with an unmoved frontier still counts."""
    rates = [1e4] * 10 + [3e4] * 6         # small rate step: frontier holds
    job = StreamJob("idkey", dim=8, sla=LOOSE)
    orch = Orchestrator(job)
    m = orch.run(_batches(16), rate_fn=lambda s: rates[min(s, len(rates) - 1)])
    frontier_changes = sum(1 for a, b in zip(m.assignments, m.assignments[1:])
                           if a != b)
    assert frontier_changes == 0, "the frontier view must not move here"
    assert m.codecs[0] == "topk_int8_ef" and m.codecs[-1] == "identity"
    assert m.migrations == 1, (
        "the codec-only swap is a plan-identity change and must be counted")
    assert len(m.plan_identities) == len(m.codecs) == 16


def test_windowed_sla_recovers_within_an_orchestrated_run():
    """Acceptance: a windowed-clean SLA report returns ok()==True after
    earlier violations age out — inside a live run, with the tracker
    window wired through StreamJob."""
    # 30s latency budget: no real batch on any machine comes close, so
    # the only violations are the seeded burst below (deterministic)
    job = StreamJob("win", dim=8, sla=SLA(max_latency_s=30.0), sla_window=8)
    orch = Orchestrator(job)
    # an earlier violation burst on the tracker the run inherits (the
    # deterministic stand-in for a compile/stall stretch)
    for _ in range(5):
        orch.sla.observe(100.0, 1e4)
    assert not orch.sla.ok()
    orch.run(_batches(30), rate_fn=lambda s: 1e4)
    assert orch.sla.violations == 5
    assert orch.sla.ok(), "clean stretch must age the violations out"


def test_adaptive_ramp_identity_budget_stays_bitwise():
    """The PR 3 invariant survives the new control dimension: under a
    zero error budget the candidate set is exactly [identity], so a
    rate-ramp run (partition migrating!) stays bitwise-identical to the
    pinned all-cloud reference."""
    rates = [8e7] * 6 + [1e4] * 6
    data = _batches(12, n_per=16)
    a = Orchestrator(StreamJob("a", dim=8, sla=SLA(max_latency_s=1e3))).run(
        data, rate_fn=lambda s: rates[min(s, len(rates) - 1)],
        record_outputs=True)
    assert set(a.codecs) == {"identity"}
    b = Orchestrator(StreamJob("b", dim=8, sla=SLA(max_latency_s=1e3))).run(
        data, rate_fn=lambda s: rates[min(s, len(rates) - 1)],
        fixed_cut=0, record_outputs=True)
    assert b.migrations == 0, "a pinned reference run executes 0 migrations"
    for x, y in zip(a.outputs, b.outputs):
        for k in x:
            np.testing.assert_array_equal(x[k], y[k])
