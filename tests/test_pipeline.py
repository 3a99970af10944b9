"""Pipeline IR: partitioned execution, compile cache, placement-driven
re-partitioning, and scenario-diverse pipelines through the orchestrator."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import costmodel as cm
from repro.core import pipeline as pl
from repro.core.orchestrator import Orchestrator, StreamJob
from repro.core.placement import place_frontier, place_graph_exhaustive
from repro.streams.events import StreamBatch
from repro.streams.fusion import WindowJoin
from repro.streams.generators import DriftSpec, HyperplaneStream

RES = {"edge": cm.EDGE_NODE, "cloud": cm.CLOUD_POD}


def _batches(n, dim=8, n_per=32, seed=0, **gen_kw):
    gen = HyperplaneStream(dim=dim, seed=seed, horizon=n * n_per, **gen_kw)
    return [gen.batch(i, n_per) for i in range(n)]


def _run_cut(pipe, data, cut):
    states = pipe.init_states()
    rng = jax.random.PRNGKey(0)
    outs = []
    for b in data:
        bd = {k: jnp.asarray(v) for k, v in b.data.items()}
        bd["rng"] = rng
        states, out = pipe.run(states, bd, pipe.names[:cut])
        rng = out["rng"]
        outs.append({k: np.asarray(v) for k, v in out.items() if k != "rng"})
    return states, outs


def _assert_trees_bitwise(a, b, what):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb),
                                      err_msg=what)


# ---------------------------------------------------------------------------
# IR basics
# ---------------------------------------------------------------------------

def test_pipeline_rejects_bad_construction():
    op = pl.sketch_op(4)
    with pytest.raises(ValueError):
        pl.Pipeline([])
    with pytest.raises(ValueError):
        pl.Pipeline([op, op])
    with pytest.raises(ValueError):
        pl.Pipeline([op], fuse="welded")
    with pytest.raises(ValueError, match="unknown ops"):
        pl.Pipeline([op]).run({}, {}, frontier={"ghost"})


def test_costs_and_executor_share_the_op_list():
    pipe = pl.standard_stream_pipeline(dim=8)
    assert [c.name for c in pipe.costs()] == pipe.names
    assert pipe.names == ["normalize", "sketch", "sample", "train", "drift"]


def test_every_cut_matches_unpartitioned_reference():
    pipe = pl.standard_stream_pipeline(dim=8, sample_rate=0.7)
    data = _batches(4)
    ref_states, ref_outs = _run_cut(pipe, data, 0)
    for cut in range(1, len(pipe.names) + 1):
        states, outs = _run_cut(pipe, data, cut)
        for a, b in zip(ref_outs, outs):
            assert set(a) == set(b)
            for k in a:
                np.testing.assert_array_equal(a[k], b[k],
                                              err_msg=f"cut={cut} out[{k}]")
        for name in pipe.names:
            _assert_trees_bitwise(ref_states[name], states[name],
                                  f"cut={cut} state[{name}]")


def test_compile_cache_hit_on_cut_revisit():
    pipe = pl.standard_stream_pipeline(dim=8)
    data = _batches(3)
    states = pipe.init_states()
    rng = jax.random.PRNGKey(0)
    for b, cut in zip(data, (3, 2, 3)):       # migrate away and back
        bd = {k: jnp.asarray(v) for k, v in b.data.items()}
        bd["rng"] = rng
        states, out = pipe.run(states, bd, pipe.names[:cut])
        rng = out["rng"]
    compiles_after_first_visit = pipe.compiles
    assert pipe.cache_hits >= 2                # cut=3 revisit was free
    bd = {k: jnp.asarray(v) for k, v in data[0].data.items()}
    bd["rng"] = rng
    pipe.run(states, bd, pipe.names[:3])
    assert pipe.compiles == compiles_after_first_visit


def test_pipeline_run_rejects_non_prefix_frontier():
    """The chain's frontiers are its prefixes: a set that skips an op's
    predecessor is not downward-closed and must not run."""
    pipe = pl.standard_stream_pipeline(dim=8)
    bd = {k: jnp.asarray(v) for k, v in _batches(1)[0].data.items()}
    bd["rng"] = jax.random.PRNGKey(0)
    with pytest.raises(ValueError, match="not downward-closed"):
        pipe.run(pipe.init_states(), bd, {"normalize", "sample"})
    with pytest.raises(ValueError, match="not downward-closed"):
        pipe.check_frontier({"sketch"})
    assert set(pipe.frontiers()) == {frozenset(pipe.names[:k])
                                     for k in range(len(pipe.names) + 1)}


# ---------------------------------------------------------------------------
# placement: a Pipeline is planned like any OpGraph
# ---------------------------------------------------------------------------

def _best_prefix(pipe, spec, rate, obj):
    """The best prefix cut ``k`` (ops[:k] on the edge pool, the rest on
    the cloud pod) by pricing every prefix through the graph evaluator;
    ties go to the smaller cut."""
    edge, cloud = spec.edge_pools[0].name, spec.cloud_pools[0].name
    best = None
    for k in range(len(pipe.names) + 1):
        assign = {n: edge if i < k else cloud
                  for i, n in enumerate(pipe.names)}
        plan = cm.evaluate_graph_plan(
            pipe.costs(), pipe.flow_edges, assign, spec, rate,
            source_consumers=pipe.source_consumers,
            source_bytes=pipe.source_bytes_per_event)
        if best is None or obj.score(plan) < obj.score(best[1]):
            best = (k, plan)
    return best


def test_placement_requires_both_pool_kinds():
    pipe = pl.standard_stream_pipeline(dim=8)
    for bad in ({}, {"edge": cm.EDGE_NODE}, {"cloud": cm.CLOUD_POD}):
        with pytest.raises(ValueError, match="edge.*cloud|cloud.*edge"):
            place_frontier(pipe, bad, 1e4)


def _two_by_two_spec():
    edge2 = cm.Resource("edge2", "edge", chips=2, flops=4e12, mem_bw=100e9,
                        mem_cap=8e9, net_bw=2e9, net_latency=10e-3,
                        energy_w=20.0)
    cloud2 = cm.Resource("cloud2", "cloud", chips=16, net_latency=0.5e-3,
                         energy_w=220.0)
    return cm.ClusterSpec(
        pools=[cm.EDGE_NODE, edge2, cm.CLOUD_POD, cloud2],
        links=[cm.Link("edge2", "cloud2", bw=2e9, latency=5e-3),
               cm.Link("edge", "edge2", bw=2e9, latency=2e-3)])


@pytest.mark.parametrize("rate", [1e2, 1e4, 1e6])
def test_pipeline_places_across_every_pool(rate):
    """A Pipeline job on a 2-edge/2-cloud spec is placed across every
    pool (not the first of each kind): its plan scores as well as the
    all-assignments oracle, and never worse than the best plan confined
    to the first edge pool and the first cloud pod."""
    orch = Orchestrator(StreamJob("pools", dim=8,
                                  cluster=_two_by_two_spec()))
    pipe, obj = orch.pipeline, orch.job.objective
    d = orch.controller.initial_plan(rate)
    oracle = place_graph_exhaustive(pipe, orch.cluster, rate, obj)
    assert obj.score(d.plan) <= obj.score(oracle) * 1.0001
    assert d.frontier == frozenset(pipe.names[:d.cut])
    first = cm.ClusterSpec(pools=[cm.EDGE_NODE, cm.CLOUD_POD])
    _, collapsed = _best_prefix(pipe, first, rate, obj)
    assert obj.score(d.plan) <= obj.score(collapsed) * 1.0001
    # the second pools are the stronger ones here: the plan uses them
    assert set(d.assignment.values()) - {"edge", "cloud"}


@pytest.mark.parametrize("rate", [1e3, 1e5, 1e7])
def test_orchestrator_default_pipeline_plans_best_prefix(rate):
    """A default StreamJob (the standard chain on the one-edge/one-cloud
    spec) takes the best prefix cut as its initial plan."""
    orch = Orchestrator(StreamJob("default", dim=8))
    d = orch.controller.initial_plan(rate)
    k, plan = _best_prefix(orch.pipeline, orch.cluster, rate,
                           orch.job.objective)
    assert d.frontier == frozenset(orch.pipeline.names[:k])
    assert d.cut == k
    assert d.assignment == plan.assignment


def test_stream_job_defaults_to_edge_cloud_spec():
    """With no cluster and no membership directory the job runs on
    ClusterSpec.edge_cloud(): one EDGE_NODE pool and one CLOUD_POD."""
    orch = Orchestrator(StreamJob("default", dim=8))
    default = cm.ClusterSpec.edge_cloud()
    assert dict(orch._base_cluster.pools) == dict(default.pools)
    assert orch.cluster.pools == {"edge": cm.EDGE_NODE,
                                  "cloud": cm.CLOUD_POD}
    assert dict(orch.controller.resources.pools) == dict(default.pools)


# ---------------------------------------------------------------------------
# acceptance: migration decisions observably change execution
# ---------------------------------------------------------------------------

def test_rate_spike_moves_cut_and_execution_matches_reference():
    """A 300x rate spike makes the offload controller move the cut; the
    orchestrator re-fuses segments mid-stream, and every per-batch result
    is bitwise-identical to a fixed-cut reference run."""
    def rate_fn(step):
        return 1e4 if step < 10 else 3e6

    data = _batches(30, dim=16, n_per=64)
    orch = Orchestrator(StreamJob("mig", dim=16))
    m = orch.run(data, rate_fn=rate_fn, record_outputs=True)

    assert m.migrations >= 1, "spike must migrate the cut"
    assert len(set(m.cuts)) >= 2, "cut must actually change what runs where"
    assert m.cuts[0] > m.cuts[-1], "spike pushes work off the edge"
    assert any("repartition" in d for d in m.decisions)

    ref = Orchestrator(StreamJob("ref", dim=16))
    mr = ref.run(data, rate_fn=rate_fn, fixed_cut=0, record_outputs=True)
    assert len(m.outputs) == len(mr.outputs)
    for a, b in zip(m.outputs, mr.outputs):
        for k in a:
            np.testing.assert_array_equal(
                a[k], b[k], err_msg=f"migrated run diverged on {k}")
    assert m.preq == mr.preq


# ---------------------------------------------------------------------------
# scenario diversity: non-default pipelines through Orchestrator.run
# ---------------------------------------------------------------------------

def test_hashing_pca_sketch_pipeline_runs_and_migrates_cleanly():
    """Sparse events -> feature hashing -> streaming PCA -> moments sketch:
    an unsupervised volume-reduction job (no labels, no learner)."""
    dim, k = 32, 4
    pipe = pl.Pipeline([pl.hash_op(dim), pl.pca_op(dim, k), pl.sketch_op(k)])
    rng = np.random.default_rng(0)
    data = []
    for i in range(12):
        ids = rng.integers(0, 10_000, (64, 8)).astype(np.int32)
        vals = rng.normal(size=(64, 8)).astype(np.float32)
        data.append(StreamBatch(data={"ids": ids, "vals": vals},
                                ts=np.arange(64) + 64.0 * i))
    job = StreamJob("hash-pca", dim=dim, pipeline=pipe)
    orch = Orchestrator(job)
    m = orch.run(data, rate_fn=lambda s: 1e4)
    assert m.events == 12 * 64
    assert m.preq is None                      # no learner op -> no preq
    assert int(orch.states["sketch"].n) == 12 * 64   # sketch accumulated
    assert orch.states["pca"].w.shape == (dim, k)


def test_fusion_fed_pipeline_runs_through_orchestrator():
    """WindowJoin-fused side channel -> concat -> normalize -> train: the
    multi-stream S2CE input interface feeding a supervised job."""
    dim, side = 8, 3
    join = WindowJoin(tolerance=5.0)
    rng = np.random.default_rng(1)
    base = _batches(15, dim=dim, n_per=32, seed=2)
    data = []
    for b in base:
        right = StreamBatch(
            data={"x": rng.normal(size=(32, side)).astype(np.float32)},
            ts=np.asarray(b.ts))
        join.push_right(right)
        joined, matched = join.join_left(b)
        assert matched.all()
        data.append(joined)

    pipe = pl.Pipeline([
        pl.concat_op("joined", dim + side),
        pl.normalize_op(dim + side),
        pl.logreg_train_op(dim + side),
    ])
    job = StreamJob("fusion-fed", dim=dim + side, pipeline=pipe)
    m = Orchestrator(job).run(data, rate_fn=lambda s: 1e4)
    assert m.events == 15 * 32
    assert m.preq is not None and m.preq["accuracy"] > 0.6
    assert m.preq["n"] == 15 * 32
