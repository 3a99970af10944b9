"""Polynomial DP placement (ROADMAP item 5): differential tests of
``place_frontier`` against the exhaustive oracles, the exhaustive-oracle
size cap, codec tie/dedup regressions, placement edge cases, and the
measured-operator-cost loop."""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import costmodel as cm
from repro.core import selftune
from repro.core.offload import OffloadController
from repro.core.orchestrator import Orchestrator, StreamJob
from repro.core.pipeline import Op, OpGraph, fanout_stream_graph
from repro.core.placement import (Objective, frontier_plans, place_frontier,
                                  place_frontier_oracle,
                                  place_graph_exhaustive)
from repro.core.sla import SLA
from repro.streams.generators import HyperplaneStream

OBJ = Objective()


# ---------------------------------------------------------------------------
# builders
# ---------------------------------------------------------------------------

def _noop(s, b):
    return s, {}


def random_graph(rng, n_ops):
    """Random DAG: op j reads 1-3 random earlier channels (30% also the
    source), 80% edge-capable."""
    ops = []
    for j in range(n_ops):
        reads = ["src"] if j == 0 else None
        if j > 0:
            k = rng.integers(1, min(j, 3) + 1)
            parents = sorted(rng.choice(j, size=k, replace=False).tolist())
            reads = [f"k{i}" for i in parents]
            if rng.random() < 0.3:
                reads.append("src")
        cost = cm.OperatorCost(
            name=f"op{j}",
            flops_per_event=float(rng.integers(10, 10**7)),
            bytes_per_event=float(rng.integers(8, 4096)),
            out_bytes_per_event=float(rng.integers(1, 2048)),
            edge_capable=bool(rng.random() < 0.8),
        )
        ops.append(Op(name=f"op{j}", fn=_noop, init=dict,
                      reads=reads, writes=[f"k{j}"], cost=cost))
    return OpGraph(ops)


def multipool_spec(codec=None):
    """2 edge pools / 2 cloud pods with declared (partly lossy) links."""
    pools = {
        "edge_a": cm.Resource("edge_a", "edge", chips=1, flops=2e12,
                              mem_bw=4e11, mem_cap=8e9, net_bw=1e9,
                              energy_w=30.0),
        "edge_b": cm.Resource("edge_b", "edge", chips=1, flops=1e12,
                              mem_bw=2e11, mem_cap=4e9, net_bw=5e8,
                              energy_w=15.0),
        "cloud": cm.Resource("cloud", "cloud", chips=4, flops=5e12,
                             mem_bw=8e11, mem_cap=32e9, net_bw=1e10,
                             energy_w=300.0),
        "cloud_b": cm.Resource("cloud_b", "cloud", chips=8, flops=5e12,
                               mem_bw=8e11, mem_cap=64e9, net_bw=1e10,
                               energy_w=500.0),
    }
    links = [cm.Link("edge_a", "cloud", bw=2e8, latency=0.03),
             cm.Link("edge_b", "cloud", bw=1e8, latency=0.05),
             cm.Link("edge_a", "edge_b", bw=5e8, latency=0.005)]
    spec = cm.ClusterSpec(pools, links=links)
    if codec:
        spec = spec.with_uplink_codec(codec)
    return spec


def chain_graph(n_ops, edge_capable_all=True):
    ops = []
    for j in range(n_ops):
        cost = cm.OperatorCost(f"op{j}", 1e4 * (j + 1), 256.0, 128.0,
                               edge_capable=edge_capable_all or j != 0)
        ops.append(Op(name=f"op{j}", fn=_noop, init=dict,
                      reads=["src"] if j == 0 else [f"k{j - 1}"],
                      writes=[f"k{j}"], cost=cost))
    return OpGraph(ops)


# ---------------------------------------------------------------------------
# tentpole: DP == oracle / enumeration
# ---------------------------------------------------------------------------

def test_dp_matches_full_oracle_on_random_dags():
    """DP score matches the all-assignments oracle (which also searches
    non-frontier placements — frontier optimality within the lattice is
    all the search promises, so compare through the enumeration's best
    frontier plan AND check it against the full oracle's)."""
    rng = np.random.default_rng(0)
    spec = multipool_spec()
    for seed in range(12):
        g = random_graph(np.random.default_rng(seed), 2 + seed % 4)
        rate = float(rng.choice([1e3, 1e4, 1e5]))
        plan_dp, frontier_dp = place_frontier(g, spec, rate, OBJ)
        plan_en, frontier_en = place_frontier_oracle(g, spec, rate, OBJ)
        assert plan_dp.assignment == plan_en.assignment, f"seed {seed}"
        assert frontier_dp == frontier_en, f"seed {seed}"
        oracle = place_graph_exhaustive(g, spec, rate, OBJ)
        best_frontier = min((p for _, p in frontier_plans(g, spec, rate, OBJ)),
                            key=OBJ.score)
        assert OBJ.score(plan_dp) <= OBJ.score(best_frontier) * 1.0001
        # oracle may beat the lattice (non-downward-closed assignment);
        # never the other way around
        assert OBJ.score(oracle) <= OBJ.score(plan_dp) * 1.0001


def test_dp_matches_enumeration_8ops_plan_identical():
    spec = multipool_spec()
    for seed in (3, 11, 27):
        g = random_graph(np.random.default_rng(seed), 8)
        plan_dp, frontier_dp = place_frontier(g, spec, 2e4, OBJ)
        plan_en, frontier_en = place_frontier_oracle(g, spec, 2e4, OBJ)
        assert plan_dp.assignment == plan_en.assignment, f"seed {seed}"
        assert frontier_dp == frontier_en
        assert plan_dp.uplink_codec == plan_en.uplink_codec
        assert OBJ.score(plan_dp) == pytest.approx(OBJ.score(plan_en))


def test_dp_codec_ladder_matches_enumeration():
    """With codec candidates the winning (frontier, pools, codec) triple
    is identical between engines, including the tie direction."""
    spec = multipool_spec()
    codecs = ["topk_int8_ef", "identity", "int8_ef"]   # adverse order
    for seed in (1, 5, 9, 16):
        g = random_graph(np.random.default_rng(seed), 3 + seed % 4)
        plan_dp, f_dp = place_frontier(g, spec, 5e4, OBJ, codecs)
        plan_en, f_en = place_frontier_oracle(g, spec, 5e4, OBJ, codecs)
        assert plan_dp.assignment == plan_en.assignment, f"seed {seed}"
        assert plan_dp.uplink_codec == plan_en.uplink_codec, f"seed {seed}"
        assert f_dp == f_en


def test_dp_small_cases_certified_exact():
    """On differential-test sizes the label fronts are far below the
    width cap: the sweep is exhaustive and says so via ``truncated``."""
    spec = multipool_spec()
    g = random_graph(np.random.default_rng(2), 6)
    stats = {}
    place_frontier(g, spec, 1e4, OBJ, stats=stats)
    assert stats["truncated"] is False
    assert 0 < stats["labels_peak"] <= 4096
    assert stats["labels_expanded"] > 0


def test_dp_beam_degrades_loudly_not_silently():
    """A tiny ``max_labels`` clips the exact sweep — the result is still
    a valid plan but ``truncated`` flags that optimality is no longer
    certified."""
    spec = multipool_spec()
    g = random_graph(np.random.default_rng(0), 8)
    stats = {}
    plan, frontier = place_frontier(g, spec, 1e4, OBJ, max_labels=1,
                                    stats=stats)
    assert stats["truncated"] is True
    assert set(plan.assignment) == set(g.names)
    exact, _ = place_frontier(g, spec, 1e4, OBJ)
    assert OBJ.score(exact) <= OBJ.score(plan) * 1.0001


# ---------------------------------------------------------------------------
# satellite: exhaustive-oracle size cap
# ---------------------------------------------------------------------------

def test_place_graph_exhaustive_size_cap():
    g = chain_graph(40)
    with pytest.raises(ValueError, match=r"would enumerate .*~1e.*max_states"):
        place_graph_exhaustive(g, multipool_spec(), 1e4, OBJ)
    small = chain_graph(3)
    plan = place_graph_exhaustive(small, multipool_spec(), 1e4, OBJ,
                                  max_states=1000)
    assert set(plan.assignment) == set(small.names)


# ---------------------------------------------------------------------------
# satellite: codec dedup + most-faithful ties
# ---------------------------------------------------------------------------

def test_frontier_plans_no_duplicate_frontiers_under_codec_ties():
    """When every uplink declares its own codec, the blanket candidates
    collapse to one effective spec: each frontier must appear exactly
    once (the historical bug yielded one duplicate plan per redundant
    candidate)."""
    spec = multipool_spec(codec="int8_ef")    # every uplink now declared
    g = random_graph(np.random.default_rng(7), 4)
    plans = list(frontier_plans(g, spec, 1e4, OBJ,
                                codecs=["topk_int8_ef", "int8_ef",
                                        "identity"]))
    frontiers = [f for f, _ in plans]
    assert len(frontiers) == len(set(frontiers))
    assert len(frontiers) == sum(1 for _ in g.frontiers())


def test_codec_score_ties_resolve_most_faithful_first():
    """A plan with no uplink crossing scores identically under every
    codec: the DP and its oracle must pick the most faithful candidate,
    whatever order the candidates were passed in."""
    g = chain_graph(3)
    # roomy edge: everything fits on the edge pool, no crossing
    edge = cm.Resource("edge", "edge", chips=4, flops=1e13, mem_bw=8e11,
                       mem_cap=64e9, net_bw=1e10, energy_w=10.0)
    spec = cm.ClusterSpec(pools=[edge, cm.CLOUD_POD])
    for place in (place_frontier, place_frontier_oracle):
        plan, frontier = place(
            g, spec, 1e3, OBJ, codecs=["topk_int8_ef", "int8_ef", "identity"])
        assert frontier == frozenset(g.names), place.__name__
        assert plan.uplink_codec == "identity", place.__name__


def test_unknown_codec_name_raises():
    g = chain_graph(3)
    for place in (place_frontier, place_frontier_oracle):
        with pytest.raises(ValueError):
            place(g, multipool_spec(), 1e4, OBJ, codecs=["no_such_codec"])


# ---------------------------------------------------------------------------
# satellite: placement edge cases
# ---------------------------------------------------------------------------

def test_single_kind_cluster_raises_for_both_engines():
    g = chain_graph(3)
    edge_only = cm.ClusterSpec(pools=[cm.EDGE_NODE])
    cloud_only = cm.ClusterSpec(pools=[cm.CLOUD_POD])
    for spec in (edge_only, cloud_only):
        for place in (place_frontier, place_frontier_oracle):
            with pytest.raises(ValueError, match="at least one"):
                place(g, spec, 1e4, OBJ)


def test_disconnected_components_agree():
    """Two source-only chains share no channels: the frontier lattice is
    a product of the per-component lattices and both engines walk it to
    the same plan."""
    ops = []
    for comp in ("a", "b"):
        for j in range(3):
            cost = cm.OperatorCost(f"{comp}{j}", 5e3 * (j + 1), 128, 64)
            ops.append(Op(name=f"{comp}{j}", fn=_noop, init=dict,
                          reads=["src"] if j == 0 else [f"{comp}k{j - 1}"],
                          writes=[f"{comp}k{j}"], cost=cost))
    g = OpGraph(ops)
    spec = multipool_spec()
    plan_dp, f_dp = place_frontier(g, spec, 1e4, OBJ)
    plan_en, f_en = place_frontier_oracle(g, spec, 1e4, OBJ)
    assert plan_dp.assignment == plan_en.assignment
    assert f_dp == f_en


def test_edge_incapable_root_forces_all_cloud():
    """If the DAG's root op cannot run on the edge, downward-closure
    makes the empty frontier the only feasible one — both engines must
    find it rather than an infeasible edge placement."""
    g = chain_graph(4, edge_capable_all=False)   # op0 edge_capable=False
    spec = multipool_spec()
    for place in (place_frontier, place_frontier_oracle):
        plan, frontier = place(g, spec, 1e4, OBJ)
        assert frontier == frozenset(), place.__name__
        assert plan.feasible, place.__name__
        assert all(spec[p].kind == "cloud"
                   for p in plan.assignment.values()), place.__name__


# ---------------------------------------------------------------------------
# controller integration
# ---------------------------------------------------------------------------

def test_controller_dp_vs_enumerate_identical_histories():
    """Every plan the controller adopts along a rate trace (with codec
    re-admission on) is the frontier oracle's plan at that decision's
    rate under the codec it took: same assignment, same frontier."""
    g = fanout_stream_graph(dim=8)
    spec = multipool_spec()
    ctl = OffloadController(g, spec, codec="topk_int8_ef",
                            sla_spec=SLA(max_latency_s=1e3,
                                         error_budget=11.0),
                            cooldown=1, codec_cooldown=1)
    ctl.initial_plan(5e6)
    for step, rate in enumerate([5e6, 1e3, 5e6, 1e3, 5e6, 2e4, 5e6, 1e3]):
        ctl.observe(step, rate)
    assert ctl.migrations() > 0
    for d in ctl.history:
        plan, frontier = place_frontier_oracle(g, spec, d.rate, OBJ,
                                               codecs=[d.codec])
        assert d.assignment == plan.assignment, (d.step, d.reason)
        assert d.frontier == frontier, (d.step, d.reason)


# ---------------------------------------------------------------------------
# measured operator costs (self-tuning loop)
# ---------------------------------------------------------------------------

def _batch(dim=8, n=32, seed=0):
    gen = HyperplaneStream(dim=dim, seed=seed, horizon=n)
    b = gen.batch(0, n)
    bd = {k: jnp.asarray(v) for k, v in b.data.items()}
    bd["rng"] = __import__("jax").random.PRNGKey(0)
    return bd


def test_measure_operator_costs_measures_and_preserves_flags():
    g = fanout_stream_graph(dim=8)
    measured, notes = selftune.measure_operator_costs(g, _batch())
    assert measured, f"nothing measured (notes: {notes})"
    declared = {op.name: op.cost for op in g.ops}
    for name, c in measured.items():
        assert c.flops_per_event > 0
        assert c.bytes_per_event > 0
        assert c.edge_capable == declared[name].edge_capable
    if "drift" in measured:
        assert measured["drift"].edge_capable is False


def test_set_measured_costs_validates_and_clears():
    g = fanout_stream_graph(dim=8)
    declared = g.costs()
    with pytest.raises(ValueError, match="unknown ops"):
        g.set_measured_costs({"ghost": declared[0]})
    # install an override, see it in costs(), clear it back
    from dataclasses import replace
    g.set_measured_costs({"normalize": replace(declared[0],
                                               flops_per_event=123.0,
                                               edge_capable=False)})
    assert g.cost_of("normalize").flops_per_event == 123.0
    # semantic flag survives the override
    assert g.cost_of("normalize").edge_capable is True
    g.set_measured_costs(None)
    assert g.cost_of("normalize").flops_per_event == \
        declared[0].flops_per_event


def test_orchestrator_measured_costs_end_to_end():
    gen = HyperplaneStream(dim=8, seed=1, horizon=96)
    batches = [gen.batch(i, 32) for i in range(3)]
    job = StreamJob("measured", dim=8, cluster=multipool_spec(),
                    measured_costs=True)
    m = Orchestrator(job).run(batches)
    assert any(d.startswith("0:measured-costs") for d in m.decisions), \
        m.decisions
    assert m.events == 96


# ---------------------------------------------------------------------------
# per-link energy model: the DP mirrors the evaluator's energy term
# ---------------------------------------------------------------------------

def energy_spec(epb_scale: float) -> cm.ClusterSpec:
    """The multipool topology with per-link transmit energy declared
    (different joules/byte per link, scaled by epb_scale)."""
    base = multipool_spec()
    links = [cm.Link("edge_a", "cloud", bw=2e8, latency=0.03,
                     energy_per_byte=3e-7 * epb_scale),
             cm.Link("edge_b", "cloud", bw=1e8, latency=0.05,
                     energy_per_byte=8e-7 * epb_scale),
             cm.Link("edge_a", "edge_b", bw=5e8, latency=0.005,
                     energy_per_byte=1e-7 * epb_scale)]
    return cm.ClusterSpec(dict(base.pools), links=links)


def test_dp_matches_enumeration_under_link_energy():
    """With energy_per_byte on the links AND an energy-weighted
    objective, DP and enumeration must still be plan-identical — the DP
    tables mirror the evaluator's link-energy arithmetic exactly."""
    obj = Objective(latency_weight=1.0, energy_weight=25.0)
    for scale in (0.0, 1.0, 100.0):
        spec = energy_spec(scale)
        for seed in (1, 5, 13, 21):
            g = random_graph(np.random.default_rng(seed), 6)
            plan_dp, f_dp = place_frontier(g, spec, 1e4, obj)
            plan_en, f_en = place_frontier_oracle(g, spec, 1e4, obj)
            assert plan_dp.assignment == plan_en.assignment, \
                f"scale {scale} seed {seed}"
            assert f_dp == f_en
            assert obj.score(plan_dp) == pytest.approx(obj.score(plan_en))
            assert plan_dp.energy_w == pytest.approx(plan_en.energy_w)


def test_dp_energy_term_matches_evaluator_repricing():
    """The DP's internal energy accumulation must agree with pricing its
    winning assignment through evaluate_graph_plan (the differential
    oracle for the satellite's new energy term)."""
    spec = energy_spec(10.0)
    obj = Objective(latency_weight=1.0, energy_weight=25.0)
    for seed in (2, 9):
        g = random_graph(np.random.default_rng(seed), 7)
        plan_dp, _ = place_frontier(g, spec, 1e4, obj)
        repriced = cm.evaluate_graph_plan(
            g.costs(), g.flow_edges, plan_dp.assignment, spec, 1e4,
            source_consumers=g.source_consumers,
            source_bytes=g.source_bytes_per_event)
        assert plan_dp.energy_w == pytest.approx(repriced.energy_w)
