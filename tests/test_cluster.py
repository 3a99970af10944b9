"""ClusterSpec topology API: multi-pool placement vs the exhaustive
oracle, link-attached uplink codecs (pricing + SLA admission + tested
error bounds under composition), critical-path DAG latency, and parity
of two-pool plans between a flat resource dict and the explicit spec."""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import codecs as cd
from repro.core import costmodel as cm
from repro.core import pipeline as pl
from repro.core.offload import OffloadController
from repro.core.orchestrator import Orchestrator, StreamJob
from repro.core.placement import (Objective, frontier_plans, place_frontier,
                                  place_graph_exhaustive)
from repro.core.sla import SLA, pick_codec
from repro.streams.generators import HyperplaneStream

EDGE_B = cm.Resource("edge_b", "edge", chips=1, flops=1e12, mem_bw=40e9,
                     mem_cap=2e9, net_bw=0.5e9, net_latency=35e-3,
                     energy_w=10.0)
CLOUD_B = cm.Resource("cloud_b", "cloud", chips=64, flops=cm.CLOUD_POD.flops,
                      mem_bw=cm.CLOUD_POD.mem_bw, mem_cap=16e9,
                      net_bw=cm.CLOUD_POD.net_bw, net_latency=0.5e-3,
                      energy_w=220.0)


def multipool_spec(codec: str = "identity") -> cm.ClusterSpec:
    """2 edge pools + 2 cloud pods with explicit, codec-carrying uplinks."""
    return cm.ClusterSpec(
        pools=[cm.EDGE_NODE, EDGE_B, cm.CLOUD_POD, CLOUD_B],
        links=[cm.Link("edge", "cloud", bw=1e9, latency=20e-3, codec=codec),
               cm.Link("edge", "cloud_b", bw=0.8e9, latency=25e-3,
                       codec=codec),
               cm.Link("edge_b", "cloud", bw=0.5e9, latency=35e-3,
                       codec=codec),
               cm.Link("edge_b", "cloud_b", bw=0.5e9, latency=40e-3,
                       codec=codec),
               cm.Link("edge", "edge_b", bw=2e9, latency=5e-3)])


# ---------------------------------------------------------------------------
# ClusterSpec construction + topology views
# ---------------------------------------------------------------------------

def test_cluster_spec_is_a_resource_mapping():
    spec = multipool_spec()
    assert set(spec) == {"edge", "edge_b", "cloud", "cloud_b"}
    assert spec["edge_b"] is EDGE_B
    assert len(spec) == 4
    assert [r.name for r in spec.edge_pools] == ["edge", "edge_b"]
    assert [r.name for r in spec.cloud_pools] == ["cloud", "cloud_b"]
    assert spec.default_source() == "edge"
    # legacy flat dicts coerce; an existing spec passes through untouched
    assert cm.ClusterSpec.of(spec) is spec
    coerced = cm.ClusterSpec.of({"edge": cm.EDGE_NODE,
                                 "cloud": cm.CLOUD_POD})
    assert list(coerced) == ["edge", "cloud"]


def test_cluster_spec_rejects_links_to_unknown_pools():
    with pytest.raises(ValueError, match="unknown pool"):
        cm.ClusterSpec(pools=[cm.EDGE_NODE],
                       links=[cm.Link("edge", "nope", bw=1e9, latency=1e-3)])


def test_declared_and_default_links():
    spec = multipool_spec("int8_ef")
    ln = spec.link("edge", "cloud_b")
    assert (ln.bw, ln.latency, ln.codec) == (0.8e9, 25e-3, "int8_ef")
    # an undeclared pair derives the historical charge-the-slow-side link
    d = spec.link("cloud", "edge")
    assert d.bw == cm.EDGE_NODE.net_bw
    assert d.latency == cm.EDGE_NODE.net_latency
    assert d.codec == "identity"
    # equal net_bw ties break toward the DESTINATION, matching the old
    # `prev if prev.net_bw < res.net_bw else res` rule exactly
    a = cm.Resource("a", "edge", net_bw=1e9, net_latency=30e-3)
    b = cm.Resource("b", "cloud", net_bw=1e9, net_latency=0.2e-3)
    tie = cm.ClusterSpec(pools=[a, b])
    assert tie.link("a", "b").latency == 0.2e-3
    assert tie.link("b", "a").latency == 30e-3


def test_with_uplink_codec_rewrites_every_uplink():
    spec = multipool_spec().with_uplink_codec("topk_int8_ef")
    for e in spec.edge_pools:
        for c in spec.cloud_pools:
            assert spec.link(e.name, c.name).codec == "topk_int8_ef"
    # non-uplink links keep their codec
    assert spec.link("edge", "edge_b").codec == "identity"
    # bw/latency of declared uplinks survive the rewrite
    assert spec.link("edge", "cloud_b").bw == 0.8e9


def test_with_uplink_codec_preserves_declared_per_link_codecs():
    """A user-declared per-link codec wins over the blanket fill; only
    override=True replaces it."""
    spec = multipool_spec("int8_ef")
    filled = spec.with_uplink_codec("topk_int8_ef")
    assert filled.link("edge", "cloud").codec == "int8_ef"
    forced = spec.with_uplink_codec("topk_int8_ef", override=True)
    assert forced.link("edge", "cloud").codec == "topk_int8_ef"


def test_cluster_spec_rejects_unknown_codec_names_at_construction():
    with pytest.raises(ValueError, match="unknown uplink codec"):
        cm.ClusterSpec(pools=[cm.EDGE_NODE, cm.CLOUD_POD],
                       links=[cm.Link("edge", "cloud", bw=1e9,
                                      latency=1e-3, codec="gzip")])


# ---------------------------------------------------------------------------
# two-pool parity: the deprecated flat dict and the explicit spec price
# identically (PR 3 plans unchanged through the shim)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rate", [1e2, 1e4, 3e6])
def test_flat_dict_and_edge_cloud_spec_price_identically(rate):
    g = pl.fanout_stream_graph(dim=16)
    legacy = {"edge": cm.EDGE_NODE, "cloud": cm.CLOUD_POD}
    spec = cm.ClusterSpec.edge_cloud()
    for (f1, p1), (f2, p2) in zip(frontier_plans(g, legacy, rate),
                                  frontier_plans(g, spec, rate)):
        assert f1 == f2
        assert p1.assignment == p2.assignment
        assert p1.latency_s == pytest.approx(p2.latency_s)
        assert p1.uplink_utilization == pytest.approx(p2.uplink_utilization)
        assert p1.energy_w == pytest.approx(p2.energy_w)
        assert p1.feasible == p2.feasible


# ---------------------------------------------------------------------------
# multi-pool placement vs the exhaustive oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rate", [1e2, 1e4, 1e6])
def test_multipool_frontier_search_matches_oracle(rate):
    g = pl.fanout_stream_graph(dim=16)
    spec = multipool_spec()
    obj = Objective()
    best, frontier = place_frontier(g, spec, rate, obj)
    oracle = place_graph_exhaustive(g, spec, rate, obj)
    assert obj.score(best) <= obj.score(oracle) * 1.0001
    assert g.check_frontier(frontier) == frontier
    # the frontier view is exactly the edge-pool-resident ops
    edge_names = {r.name for r in spec.edge_pools}
    assert frontier == frozenset(n for n, r in best.assignment.items()
                                 if r in edge_names)


def test_multipool_assignment_splits_frontier_across_edge_pools():
    """A plan the two-pool API could not express: the raw stream is too
    fat for any uplink (all-cloud infeasible) and the two heavy thinning
    branches together exceed ONE edge pool's compute — the only feasible
    placement splits the frontier across both edge pools."""
    def op(name, flops, in_bytes, out_bytes, reads, writes,
           edge_capable=True):
        return pl.Op(name, lambda s, b: (s, b),
                     cm.OperatorCost(name, flops, in_bytes, out_bytes,
                                     edge_capable=edge_capable),
                     reads=reads, writes=writes)

    rate = 1e4
    heavy = 1.4e8          # 0.7 utilization per branch on a 2e12 pool
    g = pl.OpGraph([
        op("h1", heavy, 1e6, 4.0, ("x",), ("a",)),
        op("h2", heavy, 1e6, 4.0, ("x",), ("b",)),
        op("agg", 1e3, 16.0, 8.0, ("a", "b"), ("out",),
           edge_capable=False),      # model management stays in the cloud
    ])
    edge_a = cm.Resource("edge_a", "edge", chips=1, flops=2e12,
                         net_bw=1e9, net_latency=20e-3, energy_w=15.0)
    edge_b = cm.Resource("edge_b", "edge", chips=1, flops=2e12,
                         net_bw=1e9, net_latency=20e-3, energy_w=15.0)
    spec = cm.ClusterSpec(
        pools=[edge_a, edge_b, cm.CLOUD_POD],
        links=[cm.Link("edge_a", "edge_b", bw=1e11, latency=1e-3)])
    obj = Objective()
    plan, frontier = place_frontier(g, spec, rate, obj)
    oracle = place_graph_exhaustive(g, spec, rate, obj)
    assert plan.feasible
    assert obj.score(plan) <= obj.score(oracle) * 1.0001
    assert frontier == frozenset({"h1", "h2"})
    assert {plan.assignment["h1"], plan.assignment["h2"]} == \
        {"edge_a", "edge_b"}, "heavy branches must split across edge pools"
    assert plan.assignment["agg"] == "cloud"


def _random_dag(rng):
    """A random small operator DAG (<=5 ops) with random channel wiring
    and cost profiles (the numpy twin of test_property's hypothesis
    strategy, so the multi-pool oracle match runs even when hypothesis
    is absent)."""
    n = int(rng.integers(2, 6))
    n_src = int(rng.integers(1, 3))
    sources = [f"s{i}" for i in range(n_src)]
    ops = []
    for j in range(n):
        avail = sources + [f"k{i}" for i in range(j)]
        n_reads = int(rng.integers(0, min(3, len(avail)) + 1))
        reads = tuple(sorted(rng.choice(avail, size=n_reads, replace=False)))
        cost = cm.OperatorCost(
            f"op{j}",
            flops_per_event=float(rng.uniform(10.0, 1e7)),
            bytes_per_event=float(rng.uniform(8.0, 4096.0)),
            out_bytes_per_event=float(rng.uniform(1.0, 2048.0)),
            edge_capable=bool(rng.integers(0, 2)))
        ops.append(pl.Op(f"op{j}", lambda s, b: (s, b), cost,
                         reads=reads, writes=(f"k{j}",)))
    rate = float(10 ** rng.uniform(2, 7))
    return pl.OpGraph(ops), rate


@pytest.mark.parametrize("seed", range(12))
def test_multipool_search_matches_oracle_on_random_dags(seed):
    """Acceptance: over a 2-edge/2-cloud spec the frontier search
    (frontiers x within-kind pool assignments) matches the exhaustive
    every-op-to-every-pool oracle on random small DAGs."""
    rng = np.random.default_rng(seed)
    graph, rate = _random_dag(rng)
    spec = multipool_spec(("identity", "int8_ef", "topk_int8_ef")[seed % 3])
    obj = Objective()
    best, frontier = place_frontier(graph, spec, rate, obj)
    oracle = place_graph_exhaustive(graph, spec, rate, obj)
    assert obj.score(best) <= obj.score(oracle) * 1.0001, (
        f"seed={seed}: frontier={sorted(frontier)} "
        f"score={obj.score(best)} oracle={obj.score(oracle)} "
        f"oracle_assign={oracle.assignment}")


def test_backhaul_still_infeasible_multipool():
    g = pl.fanout_stream_graph(dim=8)
    spec = multipool_spec()
    assign = {n: "cloud" for n in g.names}
    assign["alert"] = "edge_b"               # cloud-made 'drifted' flows down
    plan = cm.evaluate_graph_plan(
        g.costs(), g.flow_edges, assign, spec, 1e3,
        source_consumers=g.source_consumers,
        source_bytes=g.source_bytes_per_event)
    assert not plan.feasible
    assert any("backhaul" in n for n in plan.notes)


# ---------------------------------------------------------------------------
# critical-path latency
# ---------------------------------------------------------------------------

def test_chain_latency_is_the_per_op_sum():
    """A chain has one path, so critical-path pricing is the per-op sum:
    every op's compute latency plus one edge->cloud hop wherever the
    stream crosses (the source sits on the edge pool)."""
    pipe = pl.standard_stream_pipeline(dim=16)
    spec = cm.ClusterSpec.edge_cloud()
    hop = spec.link("edge", "cloud").latency
    n = len(pipe.names)
    for k in range(n + 1):
        assign = {nm: ("edge" if i < k else "cloud")
                  for i, nm in enumerate(pipe.names)}
        g = cm.evaluate_graph_plan(
            pipe.costs(), pipe.flow_edges, assign, spec, 1e4,
            source_consumers=pipe.source_consumers,
            source_bytes=pipe.source_bytes_per_event)
        want = sum(c.flops_per_event / spec[assign[c.name]].total_flops
                   for c in pipe.costs()) + (hop if k < n else 0.0)
        assert g.latency_s == pytest.approx(want)


def test_parallel_branches_overlap_on_the_critical_path():
    """Two equally-assigned parallel branches must cost the max of their
    latencies, not the sum (the DAG improvement over the linear model)."""
    def op(name, flops, reads, writes):
        return pl.Op(name, lambda s, b: (s, b),
                     cm.OperatorCost(name, flops, 8.0, 8.0),
                     reads=reads, writes=writes)

    g = pl.OpGraph([
        op("src", 1e6, ("x",), ("a",)),
        op("slow", 8e6, ("a",), ("s",)),      # parallel branch 1
        op("fast", 2e6, ("a",), ("f",)),      # parallel branch 2
        op("join", 1e6, ("s", "f"), ("out",)),
    ])
    res = {"edge": cm.EDGE_NODE, "cloud": cm.CLOUD_POD}
    assign = {n: "edge" for n in g.names}
    plan = cm.evaluate_graph_plan(g.costs(), g.flow_edges, assign, res, 1e2,
                                  source_consumers=g.source_consumers)
    t = lambda f: f / cm.EDGE_NODE.total_flops
    want = t(1e6) + max(t(8e6), t(2e6)) + t(1e6)
    assert plan.latency_s == pytest.approx(want)
    # and strictly less than the old per-op sum
    assert plan.latency_s < t(1e6 + 8e6 + 2e6 + 1e6)


def test_crossing_edges_add_link_latency_on_the_path():
    """A frontier cut between producer and consumers pays the crossing
    link's latency on the path (once per hop the path takes)."""
    g = pl.fanout_stream_graph(dim=16)
    res = {"edge": cm.EDGE_NODE, "cloud": cm.CLOUD_POD}
    plans = dict(frontier_plans(g, res, 1e2))
    all_cloud = plans[frozenset()]
    norm_edge = plans[frozenset({"normalize"})]
    # both plans have exactly one uplink hop on their critical path
    assert all_cloud.latency_s >= cm.EDGE_NODE.net_latency
    assert norm_edge.latency_s >= cm.EDGE_NODE.net_latency
    assert norm_edge.latency_s < 2 * cm.EDGE_NODE.net_latency


# ---------------------------------------------------------------------------
# codec pricing + SLA admission
# ---------------------------------------------------------------------------

def test_codec_wire_bytes_ratios():
    assert cd.get_codec("identity").wire_bytes(4096) == 4096
    assert cd.get_codec("int8_ef").wire_bytes(4096) == 1024
    assert cd.get_codec("topk_ef").wire_bytes(4096) == pytest.approx(819.2)
    assert cd.get_codec("topk_int8_ef").wire_bytes(4096) == 512
    with pytest.raises(KeyError, match="unknown uplink codec"):
        cd.get_codec("gzip")


def test_parameterized_codecs_register_distinct_names():
    """Link stores only the codec NAME, so a non-default k_frac must get
    its own registry entry — otherwise plans would price the default
    parameterization while execution runs the custom one."""
    c = cd.topk_ef_codec(0.25)
    assert c.name == "topk_ef_k0.25"
    assert cd.get_codec(c.name).ratio == pytest.approx(0.5)
    assert cd.get_codec("topk_ef").ratio == pytest.approx(0.2)  # default
    both = cd.topk_int8_ef_codec(0.5)
    assert cd.get_codec(both.name).ratio == pytest.approx(0.625)
    # a parameterized name resolves even if no constructor ran for it in
    # this process (config/serialization path): built on demand
    assert cd.get_codec("topk_int8_ef_k0.05").ratio == pytest.approx(0.0625)
    spec = cm.ClusterSpec(
        pools=[cm.EDGE_NODE, cm.CLOUD_POD],
        links=[cm.Link("edge", "cloud", bw=1e9, latency=1e-3,
                       codec="topk_ef_k0.02")])
    assert spec.link("edge", "cloud").wire_bytes(4096) == pytest.approx(
        4096 * 0.04)


def test_codec_compressed_links_cut_uplink_utilization():
    g = pl.fanout_stream_graph(dim=16)
    rate = 1e4
    f = frozenset({"normalize"})
    plain = dict(frontier_plans(g, cm.ClusterSpec.edge_cloud(), rate))[f]
    coded = dict(frontier_plans(
        g, cm.ClusterSpec.edge_cloud().with_uplink_codec("topk_int8_ef"),
        rate))[f]
    assert coded.uplink_utilization == pytest.approx(
        plain.uplink_utilization * 0.125)


@pytest.mark.parametrize("budget,want", [
    (0.0, "identity"),
    (0.01, "identity"),          # below int8's tested bound -> lossless
    (0.1, "int8_ef"),
    (10.0, "topk_ef"),
    (11.0, "topk_int8_ef"),
])
def test_sla_picks_cheapest_admissible_codec(budget, want):
    c = pick_codec(SLA(error_budget=budget))
    assert c.name == want
    # the acceptance invariant: an admitted codec NEVER exceeds the budget
    assert c.error_bound <= budget + 1e-12


def test_sla_never_admits_codec_over_budget():
    for budget in np.linspace(0.0, 12.0, 97):
        c = pick_codec(SLA(error_budget=float(budget)))
        assert c.error_bound <= budget + 1e-12, (budget, c.name)


def test_pick_codec_defaults_to_identity_without_admissible_candidate():
    c = pick_codec(SLA(error_budget=0.001),
                   candidates=[cd.topk_ef_codec()])
    assert c.name == "identity"


# ---------------------------------------------------------------------------
# codec error bounds under composition (satellite): accumulated error of
# the wire round-trip stays within the bound sla.pick_codec admits by,
# mirroring the ef_roundtrip / ef_topk_roundtrip bounded-error tests
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["int8_ef", "topk_ef", "topk_int8_ef"])
@pytest.mark.parametrize("constant", [True, False])
def test_codec_accumulated_error_within_admitted_bound(name, constant):
    codec = cd.get_codec(name)
    for seed in range(3):
        rng = np.random.default_rng(seed)
        d, steps = 256, 50
        g = jnp.asarray(rng.normal(scale=1e-2, size=d).astype(np.float32))
        residual = codec.init_residual(g)
        cum_true = np.zeros(d, np.float64)
        cum_dec = np.zeros(d, np.float64)
        amax = 0.0
        for t in range(steps):
            x = (g if constant else jnp.asarray(
                rng.normal(scale=1e-2, size=d).astype(np.float32)))
            amax = max(amax, float(jnp.max(jnp.abs(x))))
            dec, residual = codec.roundtrip(residual, x)
            cum_true += np.asarray(x, np.float64)
            cum_dec += np.asarray(dec, np.float64)
        err = float(np.max(np.abs(cum_dec - cum_true)))
        # telescoping EF identity: the accumulated error IS the residual
        np.testing.assert_allclose(cum_dec + np.asarray(residual, np.float64),
                                   cum_true, rtol=1e-4, atol=1e-5)
        assert err <= codec.error_bound * amax + 1e-6, (
            f"{name} constant={constant} seed={seed}: accumulated error "
            f"{err:.3g} exceeds admitted bound "
            f"{codec.error_bound * amax:.3g}")


def test_composed_codec_beats_its_parts_on_wire_bytes():
    topk, int8, both = (cd.get_codec(n)
                        for n in ("topk_ef", "int8_ef", "topk_int8_ef"))
    assert both.ratio < min(topk.ratio, int8.ratio)
    # and its bound is the sum of its parts' bounds (shared residual)
    assert both.error_bound == pytest.approx(
        topk.error_bound + int8.error_bound)


# ---------------------------------------------------------------------------
# offload + orchestrator over a ClusterSpec
# ---------------------------------------------------------------------------

def test_offload_controller_plan_identity_includes_pools_and_codec():
    g = pl.fanout_stream_graph(dim=16)
    ctl = OffloadController(g, multipool_spec(),
                            codec="int8_ef", cooldown=1)
    d0 = ctl.initial_plan(1e3)
    assert d0.codec == "int8_ef"
    assert set(d0.assignment) == set(g.names)
    assert d0.frontier == frozenset(
        n for n, r in d0.assignment.items() if r in {"edge", "edge_b"})
    d1 = ctl.observe(1, 5e6)
    assert d1.reason == "rate_up"
    assert len(d1.frontier) < len(d0.frontier)
    assert ctl.migrations() == 1


def _batches(n, dim=8, n_per=32, seed=0):
    gen = HyperplaneStream(dim=dim, seed=seed, horizon=n * n_per)
    return [gen.batch(i, n_per) for i in range(n)]


def test_orchestrator_runs_multipool_cluster_with_lossy_codec():
    """End to end over a 2-edge/2-cloud ClusterSpec with a lossy uplink
    budget: the SLA picks the composed codec, the job completes, and the
    learner still learns through the compressed uplink."""
    dim = 8
    job = StreamJob("multi", dim=dim, cluster=multipool_spec(),
                    sla=SLA(error_budget=11.0))
    orch = Orchestrator(job)
    assert orch.codec.name == "topk_int8_ef"
    for e in orch.cluster.edge_pools:
        for c in orch.cluster.cloud_pools:
            assert orch.cluster.link(e.name, c.name).codec == "topk_int8_ef"
    m = orch.run(_batches(20, dim=dim, n_per=64), rate_fn=lambda s: 1e4)
    assert m.events == 20 * 64
    assert m.codec == "topk_int8_ef"
    assert any("codec=topk_int8_ef" in d for d in m.decisions)
    assert m.preq is not None and m.preq["accuracy"] > 0.6


def test_uplink_applied_on_empty_frontier_too():
    """The all-cloud plan is priced with the raw-event crossing codec-
    compressed, so execution must apply the codec there as well — the
    empty edge segment must not skip the uplink hook."""
    g = pl.fanout_stream_graph(dim=4)
    calls = []

    def uplink(env):
        calls.append(sorted(env))
        return env

    states = g.init_states()
    import jax
    bd = {"x": jnp.ones((8, 4), jnp.float32),
          "y": jnp.zeros((8,), jnp.int32),
          "rng": jax.random.PRNGKey(0)}
    g.run(states, dict(bd), frozenset(), uplink=uplink)       # all-cloud
    assert len(calls) == 1, "raw stream must cross the uplink once"
    g.run(states, dict(bd), frozenset({"normalize"}), uplink=uplink)
    assert len(calls) == 2
    g.run(states, dict(bd), frozenset(g.names), uplink=uplink)  # all-edge
    assert len(calls) == 2, "an all-edge plan has no uplink crossing"


def test_orchestrator_rejects_lossy_topology_under_lossless_sla():
    """A declared lossy uplink codec under a zero error budget is a
    configuration conflict the orchestrator must surface, not silently
    overwrite or silently run."""
    with pytest.raises(ValueError, match="error budget"):
        Orchestrator(StreamJob("conflict", dim=8,
                               cluster=multipool_spec("int8_ef")))
    # the same topology is fine once the budget admits the codec
    orch = Orchestrator(StreamJob("ok", dim=8,
                                  cluster=multipool_spec("int8_ef"),
                                  sla=SLA(error_budget=0.1)))
    assert orch.cluster.link("edge", "cloud").codec == "int8_ef"


def test_orchestrator_identity_codec_stays_bitwise_with_default_sla():
    """The default (zero) error budget must leave the uplink lossless:
    a lossy-budget run may diverge, but the default must stay bitwise
    with the pinned all-cloud reference (the PR 3 invariant)."""
    dim = 8
    data = _batches(6, dim=dim, n_per=32)
    a = Orchestrator(StreamJob("a", dim=dim)).run(
        data, rate_fn=lambda s: 1e4, record_outputs=True)
    assert a.codec == "identity"
    b = Orchestrator(StreamJob("b", dim=dim)).run(
        data, rate_fn=lambda s: 1e4, fixed_cut=0, record_outputs=True)
    for x, y in zip(a.outputs, b.outputs):
        for k in x:
            np.testing.assert_array_equal(x[k], y[k])


# ---------------------------------------------------------------------------
# per-link energy model (Link.energy_per_byte)
# ---------------------------------------------------------------------------

def _energy_spec(epb: float, codec: str = "identity") -> cm.ClusterSpec:
    return cm.ClusterSpec(
        pools=[cm.EDGE_NODE, cm.CLOUD_POD],
        links=[cm.Link("edge", "cloud", bw=1e9, latency=20e-3, codec=codec,
                       energy_per_byte=epb)])


def test_link_energy_per_byte_priced_into_energy_aggregate():
    """Every crossing adds wire_bytes * rate * energy_per_byte watts:
    the delta vs an energy-free link is exactly the summed link-byte
    rate times the joules-per-byte."""
    pipe = pl.standard_stream_pipeline(dim=8)
    rate, epb = 1e4, 3e-7
    assign = {n: ("edge" if i < 3 else "cloud")
              for i, n in enumerate(pipe.names)}
    for codec in ("identity", "int8_ef"):
        zero, priced = _energy_spec(0.0, codec), _energy_spec(epb, codec)
        g0 = cm.evaluate_graph_plan(
            pipe.costs(), pipe.flow_edges, assign, zero, rate,
            source_consumers=pipe.source_consumers,
            source_bytes=pipe.source_bytes_per_event)
        g1 = cm.evaluate_graph_plan(
            pipe.costs(), pipe.flow_edges, assign, priced, rate,
            source_consumers=pipe.source_consumers,
            source_bytes=pipe.source_bytes_per_event)
        # bytes/s on each link = utilization * bw (codec-compressed wire)
        want = sum(u * priced.link(*k).bw * epb
                   for k, u in g1.link_utilization.items())
        assert want > 0.0
        assert g1.energy_w - g0.energy_w == pytest.approx(want)
        # everything else is untouched by the energy term
        assert g1.latency_s == g0.latency_s
        assert g1.link_utilization == g0.link_utilization


def test_link_energy_default_zero_is_bitwise_neutral():
    """Links that don't declare energy_per_byte price exactly as before
    (the default 0.0 adds literal zero to the aggregate)."""
    pipe = pl.standard_stream_pipeline(dim=8)
    assign = {n: ("edge" if i < 2 else "cloud")
              for i, n in enumerate(pipe.names)}
    bare = cm.ClusterSpec(
        pools=[cm.EDGE_NODE, cm.CLOUD_POD],
        links=[cm.Link("edge", "cloud", bw=1e9, latency=20e-3)])
    explicit = _energy_spec(0.0)
    for spec in (bare, explicit):
        assert spec.link("edge", "cloud").energy_per_byte == 0.0
    g_bare = cm.evaluate_graph_plan(
        pipe.costs(), pipe.flow_edges, assign, bare, 1e4,
        source_consumers=pipe.source_consumers,
        source_bytes=pipe.source_bytes_per_event)
    g_expl = cm.evaluate_graph_plan(
        pipe.costs(), pipe.flow_edges, assign, explicit, 1e4,
        source_consumers=pipe.source_consumers,
        source_bytes=pipe.source_bytes_per_event)
    assert g_bare.energy_w == g_expl.energy_w


def test_energy_weighted_placement_reacts_to_link_energy():
    """With an energy-weighted objective, a sufficiently expensive
    uplink pulls the frontier toward keeping bytes off the wire — the
    chosen plan under a huge energy_per_byte must not ship MORE link
    bytes than the energy-free choice."""
    g = pl.fanout_stream_graph(dim=8)
    obj = Objective(latency_weight=1.0, energy_weight=50.0)
    free, _ = place_frontier(g, _energy_spec(0.0), 1e4, obj)
    costly, _ = place_frontier(g, _energy_spec(1e-2), 1e4, obj)
    bytes_of = lambda p, s: sum(u * s.link(*k).bw
                                for k, u in p.link_utilization.items())
    assert bytes_of(costly, _energy_spec(1e-2)) <= \
        bytes_of(free, _energy_spec(0.0)) + 1e-9
