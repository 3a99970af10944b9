"""Hypothesis property tests on system invariants."""

import numpy as np
import pytest

pytest.importorskip(
    "hypothesis", reason="property tests need hypothesis (optional dep)")
from hypothesis import given, settings, strategies as st

import jax
import jax.numpy as jnp

from repro.core import pipeline as pl
from repro.core.costmodel import CLOUD_POD, EDGE_NODE, OperatorCost
from repro.core.placement import (Objective, place_frontier,
                                  place_frontier_oracle,
                                  place_graph_exhaustive)
from repro.dist.api import logical_to_spec
from repro.dist.compression import dequantize_int8, quantize_int8
from repro.streams import sketches as sk
from repro.streams import preprocess as prep


class _FakeMesh:
    def __init__(self, shape):
        self.shape = shape

    @property
    def axis_names(self):
        return tuple(self.shape)


@settings(max_examples=60, deadline=None)
@given(
    dim=st.integers(1, 4096),
    sizes=st.tuples(st.sampled_from([2, 4, 8, 16]),
                    st.sampled_from([2, 4, 8, 16])),
)
def test_logical_to_spec_always_divides(dim, sizes):
    """Whatever the dim, the chosen mesh axes always divide it exactly."""
    mesh = _FakeMesh({"data": sizes[0], "model": sizes[1]})
    spec = logical_to_spec(("batch",), {"batch": ("data", "model")},
                           mesh, (dim,))
    part = spec[0] if len(spec) else None
    axes = (part,) if isinstance(part, str) else (part or ())
    prod = 1
    for a in axes:
        prod *= mesh.shape[a]
    assert dim % prod == 0


@settings(max_examples=40, deadline=None)
@given(st.lists(st.floats(-1e3, 1e3, allow_nan=False, width=32),
                min_size=1, max_size=256))
def test_int8_quantization_error_bound(xs):
    x = jnp.asarray(np.asarray(xs, np.float32))
    q, scale = quantize_int8(x)
    deq = dequantize_int8(q, scale)
    # symmetric quantization error is bounded by scale/2 per element
    bound = float(scale) * 0.5 + 1e-6
    assert float(jnp.max(jnp.abs(deq - x))) <= bound + 1e-5


@settings(max_examples=25, deadline=None)
@given(st.lists(st.integers(0, 1000), min_size=1, max_size=500),
       st.integers(0, 2**14 - 1))
def test_countmin_overestimates_only(ids, seed):
    rng = np.random.default_rng(seed)
    depth, width = 3, 64
    seeds = np.asarray(rng.integers(1, 2**14, (depth, 2)) * 2 + 1, np.int32)
    from repro.kernels.ref import countmin_ref
    table = np.asarray(countmin_ref(jnp.asarray(ids, jnp.int32), depth,
                                    width, seeds))
    true = np.bincount(ids, minlength=1001)
    P = 2_147_483_647
    for item in set(ids):
        est = min(table[d, ((item * int(seeds[d, 0]) + int(seeds[d, 1]))
                            % P) % width] for d in range(depth))
        assert est >= true[item]


@settings(max_examples=25, deadline=None, database=None)
@given(st.integers(1, 5), st.integers(0, 1000))
def test_welford_matches_two_pass(nbatches, seed):
    rng = np.random.default_rng(seed)
    dim = 3
    st_ = prep.norm_init(dim)
    allx = []
    for _ in range(nbatches):
        # keep |mean| ~ std so fp32 single-pass variance stays well-posed
        x = rng.normal(loc=rng.normal(), scale=2.0,
                       size=(rng.integers(4, 64), dim)).astype(np.float32)
        allx.append(x)
        st_, _ = prep.norm_update_apply(st_, jnp.asarray(x))
    cat = np.concatenate(allx)
    np.testing.assert_allclose(np.asarray(st_.mean), cat.mean(0),
                               rtol=1e-3, atol=5e-3)
    var = np.asarray(st_.m2) / max(len(cat) - 1, 1)
    # fp32 single-pass vs float64 two-pass: loose but meaningful bound
    np.testing.assert_allclose(var, cat.var(0, ddof=1), rtol=6e-2, atol=6e-2)


def _property_pipeline(kind, dim):
    if kind == "standard":
        return pl.standard_stream_pipeline(dim, sample_rate=0.5,
                                           reservoir_k=16)
    if kind == "hash_pca":
        return pl.Pipeline([pl.hash_op(dim), pl.pca_op(dim, 2),
                            pl.sketch_op(2)])
    return pl.Pipeline([pl.normalize_op(dim), pl.anomaly_op(dim, m=4),
                        pl.sketch_op(dim)])


def _property_batches(kind, dim, nbatches, seed):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(nbatches):
        if kind == "hash_pca":
            out.append({"ids": jnp.asarray(
                rng.integers(0, 1000, (16, 4)).astype(np.int32)),
                "vals": jnp.asarray(
                    rng.normal(size=(16, 4)).astype(np.float32))})
        else:
            out.append({"x": jnp.asarray(
                rng.normal(size=(16, dim)).astype(np.float32)),
                "y": jnp.asarray(
                    (rng.random(16) > 0.5).astype(np.int32))})
    return out


@settings(max_examples=8, deadline=None, database=None)
@given(kind=st.sampled_from(["standard", "hash_pca", "anomaly"]),
       dim=st.sampled_from([4, 8]),
       nbatches=st.integers(1, 3),
       seed=st.integers(0, 1000))
def test_pipeline_every_cut_bitwise_matches_reference(kind, dim, nbatches,
                                                      seed):
    """Partitioning a pipeline at ANY prefix cut — the edge/cloud split the
    orchestrator migrates at runtime — must reproduce the unpartitioned
    reference execution bitwise: final states, metrics, and every batch
    output."""
    pipe = _property_pipeline(kind, dim)
    data = _property_batches(kind, dim, nbatches, seed)

    def run(cut):
        states = pipe.init_states()
        rng = jax.random.PRNGKey(seed)
        outs = []
        for bd in data:
            bd = dict(bd)
            bd["rng"] = rng
            states, out = pipe.run(states, bd, pipe.names[:cut])
            rng = out["rng"]
            outs.append(out)
        return states, outs

    ref_states, ref_outs = run(0)
    for cut in range(1, len(pipe.names) + 1):
        states, outs = run(cut)
        for a, b in zip(jax.tree.leaves((ref_states, ref_outs)),
                        jax.tree.leaves((states, outs))):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=f"kind={kind} cut={cut} diverged from reference")


@settings(max_examples=6, deadline=None, database=None)
@given(dim=st.sampled_from([4, 8]),
       sample_rate=st.sampled_from([0.3, 0.7]),
       nbatches=st.integers(1, 2),
       seed=st.integers(0, 1000))
def test_fanout_graph_every_frontier_bitwise_matches_reference(
        dim, sample_rate, nbatches, seed):
    """Partitioning the fan-out/rejoin DAG at ANY downward-closed cut —
    including cuts that keep parallel branches on different sides — must
    reproduce the unpartitioned reference execution bitwise."""
    g = pl.fanout_stream_graph(dim, sample_rate=sample_rate)
    data = _property_batches("standard", dim, nbatches, seed)

    def run(frontier):
        states = g.init_states()
        rng = jax.random.PRNGKey(seed)
        outs = []
        for bd in data:
            bd = dict(bd)
            bd["rng"] = rng
            states, out = g.run(states, bd, frontier)
            rng = out["rng"]
            outs.append(out)
        return states, outs

    ref = run(frozenset())
    for frontier in g.frontiers():
        got = run(frontier)
        for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(got)):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=f"frontier={sorted(frontier)} diverged")


def _ident(state, batch):
    return state, batch


@st.composite
def _random_dag(draw):
    """A random small operator DAG (<=6 ops) with random channel wiring
    and random cost profiles, plus a random event rate."""
    n = draw(st.integers(2, 6))
    n_src = draw(st.integers(1, 2))
    sources = [f"s{i}" for i in range(n_src)]
    ops = []
    for j in range(n):
        avail = sources + [f"k{i}" for i in range(j)]
        reads = tuple(sorted(draw(st.sets(st.sampled_from(avail),
                                          max_size=min(3, len(avail))))))
        cost = OperatorCost(
            f"op{j}",
            flops_per_event=draw(st.floats(10.0, 1e7)),
            bytes_per_event=draw(st.floats(8.0, 4096.0)),
            out_bytes_per_event=draw(st.floats(1.0, 2048.0)),
            edge_capable=draw(st.booleans()))
        ops.append(pl.Op(f"op{j}", _ident, cost,
                         reads=reads, writes=(f"k{j}",)))
    rate = draw(st.floats(1e2, 1e7))
    return pl.OpGraph(ops), rate


@settings(max_examples=60, deadline=None, database=None)
@given(case=_random_dag())
def test_frontier_search_matches_exhaustive_oracle_on_random_dags(case):
    """The frontier-cut (downward-closed) enumeration the DP is checked
    against must find the same best score as the exhaustive
    all-assignments oracle on random small DAGs — backhaul-free
    assignments ARE the frontier cuts, so searching only antichain cuts
    loses nothing."""
    graph, rate = case
    obj = Objective()
    res = {"edge": EDGE_NODE, "cloud": CLOUD_POD}
    best, frontier = place_frontier_oracle(graph, res, rate, obj)
    oracle = place_graph_exhaustive(graph, res, rate, obj)
    assert obj.score(best) <= obj.score(oracle) * 1.0001, (
        f"frontier search lost to the oracle: frontier={sorted(frontier)} "
        f"score={obj.score(best)} oracle={obj.score(oracle)} "
        f"oracle_assign={oracle.assignment}")


def _multipool_spec(codec: str):
    from repro.core.costmodel import ClusterSpec, Link, Resource
    edge_b = Resource("edge_b", "edge", chips=1, flops=1e12, mem_bw=40e9,
                      mem_cap=2e9, net_bw=0.5e9, net_latency=35e-3,
                      energy_w=10.0)
    cloud_b = Resource("cloud_b", "cloud", chips=64, net_latency=0.5e-3,
                       energy_w=220.0)
    return ClusterSpec(
        pools=[EDGE_NODE, edge_b, CLOUD_POD, cloud_b],
        links=[Link("edge", "cloud", bw=1e9, latency=20e-3, codec=codec),
               Link("edge_b", "cloud_b", bw=0.5e9, latency=40e-3,
                    codec=codec),
               Link("edge", "edge_b", bw=2e9, latency=5e-3)])


@settings(max_examples=40, deadline=None, database=None)
@given(case=_random_dag(),
       codec=st.sampled_from(["identity", "int8_ef", "topk_int8_ef"]))
def test_multipool_frontier_search_matches_oracle_on_random_dags(case, codec):
    """The multi-pool generalization of the invariant above: over a
    2-edge-pool/2-cloud-pod ClusterSpec with codec-carrying links, the
    frontier oracle (frontiers x within-kind pool assignments) must match
    the exhaustive every-op-to-every-pool oracle — cloud->edge backhaul
    stays infeasible, so the edge-resident set of any feasible assignment
    is downward-closed and the search covers it."""
    graph, rate = case
    obj = Objective()
    spec = _multipool_spec(codec)
    best, frontier = place_frontier_oracle(graph, spec, rate, obj)
    oracle = place_graph_exhaustive(graph, spec, rate, obj)
    assert obj.score(best) <= obj.score(oracle) * 1.0001, (
        f"multi-pool frontier search lost to the oracle: "
        f"frontier={sorted(frontier)} score={obj.score(best)} "
        f"oracle={obj.score(oracle)} oracle_assign={oracle.assignment}")
    edge_pools = {r.name for r in spec.edge_pools}
    assert frontier == frozenset(
        n for n, r in best.assignment.items() if r in edge_pools)


@settings(max_examples=40, deadline=None, database=None)
@given(case=_random_dag())
def test_dp_placement_matches_oracle_on_random_dags(case):
    """The polynomial label DP must equal the exhaustive all-assignments
    oracle on random small DAGs — the invariant its frontier oracle
    carries, for the engine every plan comes from."""
    graph, rate = case
    obj = Objective()
    res = {"edge": EDGE_NODE, "cloud": CLOUD_POD}
    best, frontier = place_frontier(graph, res, rate, obj)
    oracle = place_graph_exhaustive(graph, res, rate, obj)
    assert obj.score(best) <= obj.score(oracle) * 1.0001, (
        f"DP lost to the oracle: frontier={sorted(frontier)} "
        f"score={obj.score(best)} oracle={obj.score(oracle)} "
        f"oracle_assign={oracle.assignment}")


@settings(max_examples=30, deadline=None, database=None)
@given(case=_random_dag(),
       codec=st.sampled_from(["identity", "int8_ef", "topk_int8_ef"]))
def test_dp_multipool_codec_ladder_matches_enumeration(case, codec):
    """Multi-pool + codec-candidate generalization: the DP must return
    the exact plan (assignment, frontier, codec) the frontier oracle
    returns — not just the score."""
    graph, rate = case
    obj = Objective()
    spec = _multipool_spec(codec)
    codecs = ["topk_int8_ef", codec, "identity"]
    best_dp, frontier_dp = place_frontier(graph, spec, rate, obj,
                                          codecs=codecs)
    best_en, frontier_en = place_frontier_oracle(graph, spec, rate, obj,
                                                 codecs=codecs)
    assert best_dp.assignment == best_en.assignment, (
        f"DP/enumeration diverged: dp={best_dp.assignment} "
        f"en={best_en.assignment}")
    assert frontier_dp == frontier_en
    assert best_dp.uplink_codec == best_en.uplink_codec


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 100))
def test_moments_min_max_invariants(seed):
    rng = np.random.default_rng(seed)
    m = sk.moments_init(4)
    xs = rng.normal(size=(100, 4)).astype(np.float32)
    for i in range(0, 100, 25):
        m = sk.moments_update(m, jnp.asarray(xs[i:i + 25]))
    np.testing.assert_allclose(np.asarray(m.min), xs.min(0), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(m.max), xs.max(0), rtol=1e-5)
    assert int(m.n) == 100
