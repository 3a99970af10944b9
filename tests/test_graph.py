"""Operator-DAG IR: channel-inferred dependencies, frontier (downward-
closed) cuts, per-crossing-edge pricing, frontier placement vs the
exhaustive oracle, a linear Pipeline planned as its best prefix cut,
and the orchestrator running a fan-out/rejoin graph end to end."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import costmodel as cm
from repro.core import pipeline as pl
from repro.core.offload import OffloadController
from repro.core.orchestrator import Orchestrator, StreamJob
from repro.core.placement import (Objective, frontier_plans, place_frontier,
                                  place_graph_exhaustive)
from repro.streams.generators import HyperplaneStream

RES = {"edge": cm.EDGE_NODE, "cloud": cm.CLOUD_POD}


def _batches(n, dim=8, n_per=32, seed=0, **gen_kw):
    gen = HyperplaneStream(dim=dim, seed=seed, horizon=n * n_per, **gen_kw)
    return [gen.batch(i, n_per) for i in range(n)]


def _run_graph(graph, data, frontier, seed=0):
    states = graph.init_states()
    rng = jax.random.PRNGKey(seed)
    outs = []
    for b in data:
        bd = {k: jnp.asarray(v) for k, v in b.data.items()}
        bd["rng"] = rng
        states, out = graph.run(states, bd, frontier)
        rng = out["rng"]
        outs.append({k: np.asarray(v) for k, v in out.items() if k != "rng"})
    return states, outs


# ---------------------------------------------------------------------------
# construction + dependency inference
# ---------------------------------------------------------------------------

def test_opgraph_requires_channel_declarations():
    undeclared = pl.Op("mystery", lambda s, b: (s, b),
                       cm.OperatorCost("mystery", 1.0, 1.0, 1.0))
    with pytest.raises(ValueError, match="declare reads/writes"):
        pl.OpGraph([pl.normalize_op(4), undeclared])
    # the same op is fine in a linear Pipeline (conservative chain deps)
    pl.Pipeline([pl.normalize_op(4), undeclared])


def test_opgraph_rejects_non_topological_order():
    with pytest.raises(ValueError, match="order ops topologically"):
        pl.OpGraph([pl.drift_op(), pl.normalize_op(4),
                    pl.logreg_train_op(4)])


def test_fanout_dependency_structure():
    g = pl.fanout_stream_graph(dim=8)
    assert g.names == ["normalize", "sketch", "anomaly", "sample", "train",
                       "drift", "alert"]
    assert g.parents_of("sketch") == {"normalize"}
    assert g.parents_of("anomaly") == {"normalize"}
    assert g.parents_of("train") == {"normalize", "sample"}
    assert g.parents_of("alert") == {"anomaly", "drift"}
    assert ("normalize", "anomaly") in g.flow_edges
    assert ("train", "drift") in g.flow_edges
    # raw-stream channels: x into normalize, y/rng into sample+train
    assert "x" in g.source_reads and "y" in g.source_reads
    assert g.source_consumers[0] == "normalize"


def test_frontier_validation():
    g = pl.fanout_stream_graph(dim=8)
    # parallel branches can be cut independently: anomaly without sketch
    assert g.check_frontier({"normalize", "anomaly"})
    with pytest.raises(ValueError, match="downward-closed"):
        g.check_frontier({"anomaly"})          # missing ancestor normalize
    with pytest.raises(ValueError, match="unknown"):
        g.check_frontier({"normalize", "nope"})


def test_frontier_enumeration_matches_bruteforce():
    g = pl.fanout_stream_graph(dim=8)
    fronts = set(g.frontiers())
    assert frozenset() in fronts and frozenset(g.names) in fronts
    # brute force over all subsets, keeping the downward-closed ones
    import itertools
    expect = set()
    for r in range(len(g.names) + 1):
        for combo in itertools.combinations(g.names, r):
            f = set(combo)
            if all(g.parents_of(n) <= f for n in f):
                expect.add(frozenset(f))
    assert fronts == expect
    # strictly richer than any single linear ordering's n+1 prefixes
    assert len(fronts) > len(g.names) + 1


def test_pipeline_frontiers_are_exactly_the_prefixes():
    pipe = pl.standard_stream_pipeline(dim=8)
    fronts = list(pipe.frontiers())
    assert len(fronts) == len(pipe.names) + 1
    assert set(fronts) == {frozenset(pipe.names[:k])
                           for k in range(len(pipe.names) + 1)}


# ---------------------------------------------------------------------------
# execution: every downward-closed cut is bitwise the reference
# ---------------------------------------------------------------------------

def test_every_frontier_matches_unpartitioned_reference():
    g = pl.fanout_stream_graph(dim=8, sample_rate=0.7)
    data = _batches(3)
    ref_states, ref_outs = _run_graph(g, data, frozenset())
    n_checked = 0
    for frontier in g.frontiers():
        states, outs = _run_graph(g, data, frontier)
        for a, b in zip(ref_outs, outs):
            assert set(a) == set(b)
            for k in a:
                np.testing.assert_array_equal(
                    a[k], b[k], err_msg=f"frontier={sorted(frontier)} [{k}]")
        for a, b in zip(jax.tree.leaves(ref_states),
                        jax.tree.leaves(states)):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=f"frontier={sorted(frontier)} state")
        n_checked += 1
    assert n_checked > 8            # the fan-out graph has many frontiers


def test_non_prefix_frontier_executes():
    """A cut no linear pipeline can express: anomaly stays on the edge
    while the sampler/learner branch (listed between them) offloads."""
    g = pl.fanout_stream_graph(dim=8)
    f = frozenset({"normalize", "anomaly"})
    names = g.names
    picked = sorted(names.index(n) for n in f)
    assert picked != list(range(len(picked)))   # not a prefix of the order
    _, outs = _run_graph(g, _batches(2), f)
    assert "alert" in outs[-1] and "score" in outs[-1]


def test_graph_compile_cache_hit_on_frontier_revisit():
    g = pl.fanout_stream_graph(dim=8)
    data = _batches(3)
    f1 = frozenset({"normalize", "sketch", "anomaly", "sample", "train"})
    f2 = frozenset({"normalize", "anomaly"})
    states = g.init_states()
    rng = jax.random.PRNGKey(0)
    for b, f in zip(data, (f1, f2, f1)):       # migrate away and back
        bd = {k: jnp.asarray(v) for k, v in b.data.items()}
        bd["rng"] = rng
        states, out = g.run(states, bd, f)
        rng = out["rng"]
    compiles_after_first_visit = g.compiles
    assert g.cache_hits >= 2                   # f1 revisit was free
    bd = {k: jnp.asarray(v) for k, v in data[0].data.items()}
    bd["rng"] = rng
    g.run(states, bd, f1)
    assert g.compiles == compiles_after_first_visit


@pytest.mark.parametrize("linear", [False, True])
def test_fuse_xla_segments_match_op_mode_allclose(linear):
    """Whole-segment jit (`fuse="xla"`) keeps op semantics — allclose to
    the per-op composition, though not bitwise across fusion contexts."""
    if linear:
        ref = pl.standard_stream_pipeline(dim=8)
        xla = pl.Pipeline(ref.ops, fuse="xla")
        cuts = tuple(frozenset(ref.names[:k]) for k in (0, 2, len(ref.ops)))
    else:
        ref = pl.fanout_stream_graph(dim=8)
        xla = pl.OpGraph(ref.ops, fuse="xla")
        cuts = (frozenset(), frozenset({"normalize", "anomaly"}))
    data = _batches(2)
    for cut in cuts:
        (sa, oa), (sb, ob) = (_run_pipe_or_graph(p, data, cut)
                              for p in (ref, xla))
        for a, b in zip(jax.tree.leaves((sa, oa)), jax.tree.leaves((sb, ob))):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6,
                                       err_msg=f"cut={cut}")


def _run_pipe_or_graph(p, data, cut):
    states = p.init_states()
    rng = jax.random.PRNGKey(0)
    outs = []
    for b in data:
        bd = {k: jnp.asarray(v) for k, v in b.data.items()}
        bd["rng"] = rng
        states, out = p.run(states, bd, cut)
        rng = out["rng"]
        outs.append({k: np.asarray(v) for k, v in out.items() if k != "rng"})
    return states, outs


# ---------------------------------------------------------------------------
# placement: frontier search vs exhaustive oracle, linear parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rate", [1e2, 1e4, 1e6])
def test_frontier_search_matches_graph_oracle(rate):
    g = pl.fanout_stream_graph(dim=16)
    obj = Objective()
    best, frontier = place_frontier(g, RES, rate, obj)
    oracle = place_graph_exhaustive(g, RES, rate, obj)
    assert obj.score(best) <= obj.score(oracle) * 1.0001
    assert g.check_frontier(frontier) == frontier


def test_frontier_plans_price_each_crossing_edge():
    """Cutting between normalize and its three consumers pays three
    crossing charges (normalize multicasts once per remote pool, plus the
    raw-stream channels sample/train still read), not one cut-point."""
    g = pl.fanout_stream_graph(dim=16)
    plans = dict(frontier_plans(g, RES, 1e4))
    only_norm = plans[frozenset({"normalize"})]
    all_edge_capable = plans[frozenset(
        {"normalize", "sketch", "anomaly", "sample", "train"})]
    # cutting right after normalize crosses normalize->out once plus the
    # raw stream (y labels for sample/train); cutting after train crosses
    # train->drift (8 bytes) + anomaly->alert (4 bytes) + sample's thinned
    # stream is consumed on-edge, so the uplink is far cheaper
    assert all_edge_capable.uplink_utilization < only_norm.uplink_utilization


def test_linear_pipeline_plans_unchanged_vs_prefix_cut():
    """A linear Pipeline's frontiers are its prefix cuts, and the DP
    places it at the best one: the plan and cost of pricing every prefix
    through the graph evaluator and keeping the cheapest (ties to the
    smaller cut)."""
    pipe = pl.standard_stream_pipeline(dim=16)
    obj = Objective()
    for rate in (1e2, 1e4, 3e6):
        by_cut = {}
        for k in range(len(pipe.names) + 1):
            assign = {n: ("edge" if i < k else "cloud")
                      for i, n in enumerate(pipe.names)}
            by_cut[k] = cm.evaluate_graph_plan(
                pipe.costs(), pipe.flow_edges, assign, RES, rate,
                source_consumers=pipe.source_consumers,
                source_bytes=pipe.source_bytes_per_event)
        for frontier, plan in frontier_plans(pipe, RES, rate):
            ref = by_cut[len(frontier)]
            assert frontier == frozenset(pipe.names[:len(frontier)])
            assert plan.assignment == ref.assignment
            assert plan.latency_s == pytest.approx(ref.latency_s)
            assert plan.uplink_utilization == pytest.approx(
                ref.uplink_utilization)
            assert plan.energy_w == pytest.approx(ref.energy_w)
            assert plan.feasible == ref.feasible
        best_cut = min(by_cut, key=lambda k: (obj.score(by_cut[k]), k))
        g_plan, g_frontier = place_frontier(pipe, RES, rate)
        assert len(g_frontier) == best_cut
        assert g_plan.assignment == by_cut[best_cut].assignment
        assert obj.score(g_plan) == pytest.approx(
            obj.score(by_cut[best_cut]))


def test_backhaul_assignments_are_infeasible():
    g = pl.fanout_stream_graph(dim=8)
    assign = {n: "cloud" for n in g.names}
    assign["alert"] = "edge"                   # consumes cloud-made drifted
    plan = cm.evaluate_graph_plan(
        g.costs(), g.flow_edges, assign, RES, 1e3,
        source_consumers=g.source_consumers,
        source_bytes=g.source_bytes_per_event)
    assert not plan.feasible
    assert any("backhaul" in n for n in plan.notes)


# ---------------------------------------------------------------------------
# offload controller over a graph: hysteresis on plan identity
# ---------------------------------------------------------------------------

def test_graph_offload_migrates_frontier_on_burst():
    g = pl.fanout_stream_graph(dim=16)
    ctl = OffloadController(g, RES, cooldown=1)
    d0 = ctl.initial_plan(1e3)
    assert d0.frontier == ctl.frontier and d0.cut == len(d0.frontier)
    assert len(d0.frontier) > 0, "cheap rate keeps work on the edge"
    d1 = ctl.observe(1, 5e6)                   # big burst
    assert d1.reason == "rate_up"
    assert d1.frontier < d0.frontier, "burst must shrink the edge set"
    assert ctl.migrations() == 1


def test_graph_offload_hysteresis_holds_inside_band():
    g = pl.fanout_stream_graph(dim=16)
    ctl = OffloadController(g, RES, cooldown=3)
    ctl.initial_plan(1e4)
    for step in range(1, 30):
        d = ctl.observe(step, 1e4 * (1.1 if step % 2 else 0.9))
        assert d.reason == "hold"
    assert ctl.migrations() == 0


# ---------------------------------------------------------------------------
# orchestrator: graph jobs end to end
# ---------------------------------------------------------------------------

def test_orchestrator_runs_fanout_graph_and_migrates():
    """The orchestrator plans, executes, and migrates a fan-out graph; a
    spike moves work off the edge and every per-batch output matches the
    pinned all-cloud reference bitwise."""
    def rate_fn(step):
        return 1e3 if step < 8 else 5e6

    dim = 16
    data = _batches(24, dim=dim, n_per=64)
    job = StreamJob("fan", dim=dim, pipeline=pl.fanout_stream_graph(dim))
    orch = Orchestrator(job)
    m = orch.run(data, rate_fn=rate_fn, record_outputs=True)

    assert m.events == 24 * 64
    assert m.migrations >= 1, "spike must migrate the frontier"
    assert len(set(m.assignments)) >= 2
    assert len(m.assignments[0]) > len(m.assignments[-1]), \
        "spike pushes work off the edge"
    assert any("repartition" in d for d in m.decisions)
    assert m.preq is not None                  # train op metrics surfaced

    ref = Orchestrator(StreamJob("ref", dim=dim,
                                 pipeline=pl.fanout_stream_graph(dim)))
    mr = ref.run(data, rate_fn=rate_fn, fixed_frontier=frozenset(),
                 record_outputs=True)
    assert len(m.outputs) == len(mr.outputs)
    for a, b in zip(m.outputs, mr.outputs):
        assert set(a) == set(b)
        for k in a:
            np.testing.assert_array_equal(
                a[k], b[k], err_msg=f"migrated run diverged on {k}")
    assert m.preq == mr.preq


def test_orchestrator_elastic_rescale_through_checkpoint_cycle(tmp_path):
    """A sustained overload makes the ElasticController grow, and the
    orchestrator now drives the plan through elastic.rescale_cycle:
    states round-trip a published checkpoint bitwise and land on the
    rebuilt mesh."""
    dim = 8
    data = _batches(16, dim=dim, n_per=32)
    job = StreamJob("grow", dim=dim, workers=1, max_workers=4,
                    ckpt_dir=str(tmp_path))
    orch = Orchestrator(job)
    m = orch.run(data, rate_fn=lambda s: 5e7, record_outputs=True)
    assert m.rescales >= 1
    assert m.workers > 1
    assert any("elastic-grow" in d for d in m.decisions)
    from repro.dist import checkpoint as ckpt
    assert ckpt.latest_step(tmp_path) is not None, \
        "rescale must publish a checkpoint"
    # the rescale cycle must not perturb learner state: bitwise vs a angry
    # reference run whose elastic controller is capped at 1 worker
    ref = Orchestrator(StreamJob("ref", dim=dim, workers=1, max_workers=1))
    mr = ref.run(data, rate_fn=lambda s: 5e7, record_outputs=True)
    assert mr.rescales == 0
    for a, b in zip(m.outputs, mr.outputs):
        for k in a:
            np.testing.assert_array_equal(
                a[k], b[k], err_msg=f"rescale cycle perturbed {k}")
    assert m.preq == mr.preq


def test_orchestrator_advances_rng_without_threading_op():
    """Stale-RNG regression: a pipeline with no op that threads `rng`
    must still see fresh randomness every step (the orchestrator now
    splits the key per step instead of reusing the initial one)."""
    dim = 4

    def fn(state, batch):
        noise = jax.random.normal(batch["rng"], (dim,))
        return state, {**batch, "noise": noise}

    noise = pl.Op("noise", fn,
                  cm.OperatorCost("noise", 10.0, 16.0, 4.0 * dim),
                  reads=("rng",), writes=("noise",))
    pipe = pl.Pipeline([noise])
    job = StreamJob("noisy", dim=dim, pipeline=pipe)
    m = Orchestrator(job).run(_batches(3, dim=dim), rate_fn=lambda s: 1e3,
                              record_outputs=True)
    n0, n1 = m.outputs[0]["noise"], m.outputs[1]["noise"]
    assert not np.array_equal(n0, n1), \
        "consecutive steps must not reuse the same PRNG key"
