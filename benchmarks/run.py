"""S2CE benchmark harness — one benchmark per paper claim (the paper has no
quantitative tables, so Table 1 rows / success criteria S1-S4 are the
benchmark targets; EXPERIMENTS.md maps each to its row here).

Prints ``name,us_per_call,derived`` CSV; ``--out PATH`` additionally
writes the machine-readable trajectory snapshot ``benchmarks/compare.py``
gates CI on (see ``benchmarks/README.md``). Run:
  PYTHONPATH=src python -m benchmarks.run [--quick] [--smoke]
      [--only SUBSTR] [--out BENCH_<pr>.json]
"""

import argparse
import json
import os
import subprocess
import time

import numpy as np

import jax
import jax.numpy as jnp

BENCH_SCHEMA = "s2ce-bench/1"


class BenchStat(float):
    """Median µs-per-call that IS a float (every existing ``f"{us:.2f}"``
    / arithmetic call site keeps working) but carries the full sample
    stats the JSON trajectory persists: p90, sample count, payload bytes."""

    def __new__(cls, median_us, p90_us=None, iters=1, nbytes=None):
        self = super().__new__(cls, median_us)
        self.p90_us = float(median_us if p90_us is None else p90_us)
        self.iters = int(iters)
        self.nbytes = None if nbytes is None else int(nbytes)
        return self


def _timeit(fn, *args, warmup=2, iters=10, nbytes=None):
    """Per-iteration wall-time sampling (each sample fully synchronized),
    so the persisted median/p90 are robust to scheduler noise instead of
    one mean over a single timed loop."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    samples = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        samples.append((time.perf_counter() - t0) * 1e6)
    samples.sort()
    median = samples[len(samples) // 2]
    p90 = samples[min(len(samples) - 1, int(round(0.9 * (len(samples) - 1))))]
    return BenchStat(median, p90, iters, nbytes)


def bench_s1_throughput_scaling(rows, quick):
    """S1: stream preprocessing throughput vs batch size (single host;
    host-level scaling is embarrassingly parallel at the feeder level)."""
    from repro.streams import preprocess as prep
    dim = 64
    st = prep.norm_init(dim)
    fn = jax.jit(prep.norm_update_apply)
    for n in ([1024, 8192] if quick else [1024, 8192, 65536]):
        x = jnp.asarray(np.random.default_rng(0).normal(size=(n, dim)),
                        jnp.float32)
        us = _timeit(fn, st, x)
        rows.append((f"s1_preprocess_n{n}", us, f"{n / us * 1e6:.0f} events/s"))


def bench_s2_update_latency(rows, quick):
    """S2: 'microsecond updates' — per-event model/detector update latency."""
    from repro.ml import online
    from repro.streams import drift as dd
    x1 = jnp.ones((1, 32)) * 0.1
    y1 = jnp.ones((1,), jnp.int32)
    lr_state = online.logreg_init(32)
    fn = jax.jit(online.logreg_update)
    us = _timeit(fn, lr_state, x1, y1)
    rows.append(("s2_logreg_update_1ev", us, f"{us:.1f} us/event"))

    for name, init, step in [("ddm", dd.ddm_init, dd.ddm_step),
                             ("ph", dd.ph_init, dd.ph_step)]:
        st = init()
        f = jax.jit(step)
        us = _timeit(f, st, jnp.asarray(0.0))
        rows.append((f"s2_{name}_step", us, f"{us:.1f} us/event"))
    errs = jnp.zeros((4096,))
    scan_fn = jax.jit(lambda s, e: dd.run_detector(dd.ddm_step, s, e))
    us = _timeit(scan_fn, dd.ddm_init(), errs)
    rows.append(("s2_ddm_scan4096", us, f"{us / 4096:.3f} us/event amortized"))


def bench_s3_offload(rows, quick):
    """S3: cloud<->edge shift — plan latency/energy across ingest rates and
    controller decision latency."""
    from repro.core import costmodel as cm
    from repro.core.offload import OffloadController
    from repro.core.placement import place_frontier, standard_pipeline
    res = cm.ClusterSpec.edge_cloud()
    pipe = standard_pipeline(dim=64)
    for rate in [1e3, 1e5, 1e7]:
        t0 = time.perf_counter()
        plan, frontier = place_frontier(pipe, res, rate)
        us = (time.perf_counter() - t0) * 1e6
        rows.append((f"s3_place_rate{rate:.0e}", us,
                     f"cut={len(frontier)} lat={plan.latency_s * 1e3:.2f}ms "
                     f"energy={plan.energy_w:.0f}W"))
    ctl = OffloadController(pipe, res)
    ctl.initial_plan(1e4)
    t0 = time.perf_counter()
    for step in range(100):
        ctl.observe(step, 1e4 * (1 + (step % 7)))
    us = (time.perf_counter() - t0) / 100 * 1e6
    rows.append(("s3_offload_decision", us, f"migrations={ctl.migrations()}"))


def bench_pipeline_partition(rows, quick):
    """Tentpole path: per-batch execution under a cut, cold segment
    re-fuse on migration, and cached re-partition (cut revisit)."""
    from repro.core.pipeline import standard_stream_pipeline
    pipe = standard_stream_pipeline(dim=16, sample_rate=0.5)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(256, 16)),
                    jnp.float32)
    y = jnp.asarray(np.random.default_rng(1).integers(0, 2, 256), jnp.int32)
    rng = jax.random.PRNGKey(0)
    states = pipe.init_states()

    def step(states, rng, cut):
        states, out = pipe.run(states, {"x": x, "y": y, "rng": rng},
                               pipe.names[:cut])
        return states, out["rng"]

    t0 = time.perf_counter()
    states, rng = step(states, rng, 4)        # cold: compile both segments
    cold = (time.perf_counter() - t0) * 1e6
    rows.append(("pipeline_refuse_cold", cold, f"{pipe.compiles} compiles"))
    us = _timeit(lambda s, r: step(s, r, 4)[1], states, rng, iters=20)
    rows.append(("pipeline_step_cut4", us, f"{256 / us * 1e6:.0f} events/s"))
    t0 = time.perf_counter()
    states, rng = step(states, rng, 2)        # migration: re-fuse 2 segments
    mig = (time.perf_counter() - t0) * 1e6
    states, rng = step(states, rng, 4)        # revisit: cache hit
    t1 = time.perf_counter()
    states, rng = step(states, rng, 2)
    rev = (time.perf_counter() - t1) * 1e6
    rows.append(("pipeline_migrate_cold", mig, "segment re-fuse (compile)"))
    rows.append(("pipeline_migrate_cached", rev,
                 f"{pipe.cache_hits} cache hits"))


def bench_dag_placement(rows, quick):
    """Tentpole path: frontier-cut (downward-closed) placement search over
    the fan-out/rejoin example graph vs the exhaustive all-assignments
    oracle — plans/sec and agreement."""
    from repro.core import costmodel as cm
    from repro.core.pipeline import fanout_stream_graph
    from repro.core.placement import (Objective, place_frontier,
                                      place_graph_exhaustive)
    res = {"edge": cm.EDGE_NODE, "cloud": cm.CLOUD_POD}
    g = fanout_stream_graph(dim=16)
    n_frontiers = sum(1 for _ in g.frontiers())
    obj = Objective()
    iters = 5 if quick else 20
    t0 = time.perf_counter()
    for _ in range(iters):
        plan, frontier = place_frontier(g, res, 1e4, obj)
    us = (time.perf_counter() - t0) / iters * 1e6
    rows.append(("dag_place_frontier", us,
                 f"{n_frontiers} frontiers, "
                 f"{n_frontiers / us * 1e6:.0f} plans/s, "
                 f"edge={len(frontier)}/{len(g.names)} ops"))
    n_assign = 2 ** len(g.names)
    t0 = time.perf_counter()
    for _ in range(iters):
        oracle = place_graph_exhaustive(g, res, 1e4, obj)
    us_o = (time.perf_counter() - t0) / iters * 1e6
    agree = obj.score(plan) <= obj.score(oracle) * 1.0001
    rows.append(("dag_place_exhaustive", us_o,
                 f"{n_assign} assigns, {n_assign / us_o * 1e6:.0f} plans/s, "
                 f"frontier_matches_oracle={agree}"))


def bench_dag_place_multipool(rows, quick):
    """ClusterSpec path: frontier placement over a 2-edge-pool/2-cloud-pod
    topology with codec-carrying uplinks (frontiers x within-kind pool
    assignments) vs the multi-pool exhaustive oracle."""
    from repro.core import costmodel as cm
    from repro.core.pipeline import fanout_stream_graph
    from repro.core.placement import (Objective, place_frontier,
                                      place_graph_exhaustive)
    edge_b = cm.Resource("edge_b", "edge", chips=1, flops=1e12, mem_bw=40e9,
                         mem_cap=2e9, net_bw=0.5e9, net_latency=35e-3,
                         energy_w=10.0)
    cloud_b = cm.Resource("cloud_b", "cloud", chips=64, net_latency=0.5e-3,
                          energy_w=220.0)
    spec = cm.ClusterSpec(
        pools=[cm.EDGE_NODE, edge_b, cm.CLOUD_POD, cloud_b],
        links=[cm.Link("edge", "cloud", bw=1e9, latency=20e-3,
                       codec="int8_ef"),
               cm.Link("edge_b", "cloud_b", bw=0.5e9, latency=40e-3,
                       codec="topk_int8_ef"),
               cm.Link("edge", "edge_b", bw=2e9, latency=5e-3)])
    g = fanout_stream_graph(dim=16)
    obj = Objective()
    iters = 2 if quick else 5
    t0 = time.perf_counter()
    for _ in range(iters):
        plan, frontier = place_frontier(g, spec, 1e4, obj)
    us = (time.perf_counter() - t0) / iters * 1e6
    oracle = place_graph_exhaustive(g, spec, 1e4, obj)
    agree = obj.score(plan) <= obj.score(oracle) * 1.0001
    n_assign = len(spec) ** len(g.names)
    rows.append(("dag_place_multipool", us,
                 f"{len(spec)} pools, oracle {n_assign} assigns, "
                 f"edge={len(frontier)}/{len(g.names)} ops, "
                 f"matches_oracle={agree}"))


def _dp_synthetic_dag(n_ops, seed=7):
    """Mostly-chain stream DAG with periodic diamonds and skip reads —
    large enough that frontier enumeration is astronomically infeasible
    while the frontier lattice stays non-trivial (ops near the tail are
    edge-incapable, forcing real cut decisions)."""
    from repro.core.costmodel import OperatorCost
    from repro.core.pipeline import Op, OpGraph
    rng = np.random.default_rng(seed)
    ops = []
    for j in range(n_ops):
        if j == 0:
            reads = ["src"]
        elif j % 7 in (3, 4) and j >= 2:
            reads = [f"k{j - 2}"]
        else:
            reads = [f"k{j - 1}"]
            if j % 11 == 0 and j >= 2:
                reads.append(f"k{j - 2}")
        edge_ok = not (j >= 3 * n_ops // 4 and j % 5 == 0)
        cost = OperatorCost(
            name=f"op{j}",
            flops_per_event=float(rng.integers(10**3, 10**7)),
            bytes_per_event=float(rng.integers(64, 4096)),
            out_bytes_per_event=float(rng.integers(16, 2048)),
            edge_capable=edge_ok,
        )
        ops.append(Op(name=f"op{j}", fn=lambda s, b: (s, {}),
                      init=lambda: {}, reads=reads, writes=[f"k{j}"],
                      cost=cost))
    return OpGraph(ops)


def _dp_big_spec(n_edge, n_cloud, seed=7):
    """Heterogeneous cluster: varied edge boxes, 4/8-chip pods, a sparse
    random mesh of declared edge->pod uplinks (some codec-carrying)."""
    from repro.core.costmodel import ClusterSpec, Link, Resource
    rng = np.random.default_rng(seed)
    pools = {}
    for i in range(n_edge):
        pools[f"edge{i}"] = Resource(
            f"edge{i}", "edge", chips=1,
            flops=float(rng.choice([1e12, 2e12, 4e12])),
            mem_bw=float(rng.choice([2e11, 4e11])),
            mem_cap=8e9, net_bw=float(rng.choice([5e8, 1e9])),
            energy_w=float(rng.choice([15.0, 30.0, 45.0])))
    for i in range(n_cloud):
        pools[f"pod{i}"] = Resource(
            f"pod{i}", "cloud", chips=int(rng.choice([4, 8])),
            flops=5e12, mem_bw=8e11, mem_cap=64e9, net_bw=1e10,
            energy_w=float(rng.choice([300.0, 500.0])))
    links = []
    for i in range(n_edge):
        for k in range(n_cloud):
            if rng.random() < 0.25:
                links.append(Link(
                    f"edge{i}", f"pod{k}",
                    bw=float(rng.choice([1e8, 2e8, 5e8])),
                    latency=float(rng.choice([0.02, 0.03, 0.05])),
                    codec="int8_ef" if rng.random() < 0.3 else "identity"))
    return ClusterSpec(pools, links=links)


def bench_dag_place_dp(rows, quick):
    """Polynomial-time DP placement (ROADMAP item 5). Row 1 is the CI
    tripwire: the DP must return the SAME score as the frontier
    enumeration on the multi-pool fanout graph. Row 2 places a 100-op
    synthetic DAG across a 24-pool cluster — a search space (~24^100
    assignments) no enumeration could ever touch — and reports the
    label-DP effort stats."""
    from repro.core import costmodel as cm
    from repro.core.pipeline import fanout_stream_graph
    from repro.core.placement import (Objective, place_frontier,
                                      place_frontier_oracle)
    obj = Objective()
    # row 1: DP vs enumeration on the bench_dag_place_multipool topology
    edge_b = cm.Resource("edge_b", "edge", chips=1, flops=1e12, mem_bw=40e9,
                         mem_cap=2e9, net_bw=0.5e9, net_latency=35e-3,
                         energy_w=10.0)
    cloud_b = cm.Resource("cloud_b", "cloud", chips=64, net_latency=0.5e-3,
                          energy_w=220.0)
    spec = cm.ClusterSpec(
        pools=[cm.EDGE_NODE, edge_b, cm.CLOUD_POD, cloud_b],
        links=[cm.Link("edge", "cloud", bw=1e9, latency=20e-3,
                       codec="int8_ef"),
               cm.Link("edge_b", "cloud_b", bw=0.5e9, latency=40e-3,
                       codec="topk_int8_ef"),
               cm.Link("edge", "edge_b", bw=2e9, latency=5e-3)])
    g = fanout_stream_graph(dim=16)
    iters = 2 if quick else 5
    t0 = time.perf_counter()
    for _ in range(iters):
        plan_dp, frontier_dp = place_frontier(g, spec, 1e4, obj)
    us = (time.perf_counter() - t0) / iters * 1e6
    plan_en, _ = place_frontier_oracle(g, spec, 1e4, obj)
    parity = obj.score(plan_dp) <= obj.score(plan_en) * 1.0001
    rows.append(("dag_place_dp_parity", us,
                 f"{len(spec)} pools, edge={len(frontier_dp)}/"
                 f"{len(g.names)} ops, matches_enumeration={parity}"))
    # row 2: the headline scale point — 100 ops x 24 pools
    g_big = _dp_synthetic_dag(100)
    spec_big = _dp_big_spec(8, 16)
    stats = {}
    t0 = time.perf_counter()
    plan, frontier = place_frontier(g_big, spec_big, 1e5, obj,
                                    max_labels=256, stats=stats)
    us = (time.perf_counter() - t0) * 1e6
    rows.append(("dag_place_dp_100ops", us,
                 f"{len(spec_big)} pools, edge={len(frontier)}/100 ops, "
                 f"score={obj.score(plan):.5f}, "
                 f"labels={stats.get('labels_expanded', 0)}, "
                 f"truncated={stats.get('truncated')}"))


def bench_adaptive_codec_replan(rows, quick):
    """Rate-adaptive codec control: one replan over the enlarged
    (frontier x pool x codec) search — plans/sec so CI catches a
    search-space blowup — plus the controller-level ramp decision."""
    from repro.core import costmodel as cm
    from repro.core.offload import OffloadController
    from repro.core.pipeline import fanout_stream_graph
    from repro.core.placement import Objective, place_frontier
    from repro.core.sla import SLA, codec_candidates
    edge_b = cm.Resource("edge_b", "edge", chips=1, flops=1e12, mem_bw=40e9,
                         mem_cap=2e9, net_bw=0.5e9, net_latency=35e-3,
                         energy_w=10.0)
    cloud_b = cm.Resource("cloud_b", "cloud", chips=64, net_latency=0.5e-3,
                          energy_w=220.0)
    spec = cm.ClusterSpec(pools=[cm.EDGE_NODE, edge_b, cm.CLOUD_POD, cloud_b])
    g = fanout_stream_graph(dim=16)
    sla = SLA(max_latency_s=1e3, error_budget=11.0)
    codecs = [c.name for c in codec_candidates(sla)]
    obj = Objective()
    n_frontiers = sum(1 for _ in g.frontiers())
    iters = 2 if quick else 5
    t0 = time.perf_counter()
    for _ in range(iters):
        plan, frontier = place_frontier(g, spec, 5e6, obj, codecs=codecs)
    us = (time.perf_counter() - t0) / iters * 1e6
    # the searched space: frontiers x within-kind pool products x codecs
    n_plans = sum(2 ** len(f) * 2 ** (len(g.names) - len(f))
                  for f in g.frontiers()) * len(codecs)
    rows.append(("adaptive_codec_replan", us,
                 f"{n_frontiers} frontiers x {len(codecs)} codecs = "
                 f"{n_plans} plans, {n_plans / us * 1e6:.0f} plans/s, "
                 f"codec={plan.uplink_codec}"))
    # one full escalate/de-escalate cycle through the controller
    ctl = OffloadController(g, spec, codec="topk_int8_ef", sla_spec=sla,
                            cooldown=1, codec_cooldown=1)
    ctl.initial_plan(5e6)
    t0 = time.perf_counter()
    for step, rate in enumerate([1e3, 5e6] * 5):
        ctl.observe(step, rate)
    us = (time.perf_counter() - t0) / 10 * 1e6
    codecs_seen = sorted({d.codec for d in ctl.history})
    rows.append(("adaptive_codec_observe", us,
                 f"codecs={codecs_seen} migrations={ctl.migrations()}"))


def bench_uplink_codec(rows, quick):
    """Uplink codec round-trip throughput + measured accumulated error
    vs the admitted bound, per codec."""
    from repro.core.codecs import DEFAULT_CODECS
    x = jnp.asarray(np.random.default_rng(0).normal(
        size=(16, 4096)).astype(np.float32))
    for codec in DEFAULT_CODECS:
        if codec.lossless:
            continue
        residual = codec.init_residual(x)
        fn = jax.jit(codec.roundtrip)
        us = _timeit(fn, residual, x, iters=5 if quick else 20)
        dec, r = fn(residual, x)
        err = float(jnp.max(jnp.abs(r))) / max(
            float(jnp.max(jnp.abs(x))), 1e-30)
        mb_s = x.size * 4 / us  # raw MB/s through the codec
        rows.append((f"uplink_codec_{codec.name}", us,
                     f"ratio={codec.ratio:.3f} {mb_s:.0f}MB/s "
                     f"rel_err={err:.4f}<=bound={codec.error_bound:.4f}"))


def bench_fusion_join(rows, quick):
    """WindowJoin hot path: vectorized as-of join + slice eviction."""
    from repro.streams.events import StreamBatch
    from repro.streams.fusion import WindowJoin
    j = WindowJoin(tolerance=0.5, max_buffer=20_000)
    rng = np.random.default_rng(0)
    n_rounds = 10 if quick else 30
    t0 = time.perf_counter()
    n = 0
    for i in range(n_rounds):
        ts = i * 1000.0 + np.arange(1000, dtype=np.float64)
        j.push_right(StreamBatch(
            data={"x": rng.normal(size=(1000, 8)).astype(np.float32)},
            ts=ts))
        left = StreamBatch(
            data={"x": np.zeros((500, 1), np.float32)},
            ts=i * 1000.0 + np.sort(rng.random(500) * 1000))
        j.join_left(left)
        n += 1500
    dt = time.perf_counter() - t0
    rows.append(("fusion_window_join", dt / n_rounds * 1e6,
                 f"{n / dt:.0f} events/s"))


def bench_s4_feature_matrix(rows, quick):
    """S4/Table 1: every 'Desired Platform' feature exists — import one
    representative module per row."""
    import importlib
    features = {
        "stream_integration": "repro.streams.feeder",
        "preprocessing_fusion": "repro.streams.fusion",
        "synthetic_generator": "repro.streams.generators",
        "stream_ml": "repro.ml.online",
        "stream_dl": "repro.models.model_zoo",
        "resource_mgmt": "repro.core.placement",
        "distributed": "repro.dist.sharding",
        "drift_detection": "repro.streams.drift",
        "fault_tolerance": "repro.dist.elastic",
        "self_tuning": "repro.core.selftune",
    }
    ok = sum(importlib.import_module(m) is not None for m in features.values())
    rows.append(("s4_feature_matrix", 0.0, f"{ok}/{len(features)} present"))


def bench_generators(rows, quick):
    from repro.streams.generators import HyperplaneStream, TokenStream
    g = HyperplaneStream(dim=32)
    t0 = time.perf_counter()
    n = 0
    for i in range(20):
        b = g.batch(i, 4096)
        n += b.n
    dt = time.perf_counter() - t0
    rows.append(("gen_hyperplane", dt / 20 * 1e6, f"{n / dt:.0f} events/s"))
    tg = TokenStream(vocab_size=65536, seq_len=512)
    t0 = time.perf_counter()
    toks = 0
    for i in range(10):
        b = tg.batch(i, 64)
        toks += b.data["tokens"].size
    dt = time.perf_counter() - t0
    rows.append(("gen_tokens", dt / 10 * 1e6, f"{toks / dt:.0f} tok/s"))


def bench_sketches(rows, quick):
    from repro.streams import sketches as sk
    cm_ = sk.countmin_init(4, 1024)
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 10000, 8192),
                      jnp.int32)
    us = _timeit(lambda c, i: sk.countmin_add(c, i), cm_, ids, iters=5,
                 nbytes=8192 * 4)
    rows.append(("sketch_countmin_8192", us, f"{8192 / us * 1e6:.0f} items/s"))
    us = _timeit(lambda c, i: sk.countmin_add_query(c, i), cm_, ids, iters=5,
                 nbytes=8192 * 4)
    rows.append(("sketch_countmin_addquery_8192", us,
                 f"{8192 / us * 1e6:.0f} items/s fused add+query"))


def bench_kernel_dispatch(rows, quick):
    """Stream-kernel dispatch wrappers as they run on THIS backend (jnp
    reference on CPU, fused Pallas on TPU; the JSON envelope records
    which) — the hot-path rows the perf trajectory gates."""
    from repro.dist import compression as comp
    from repro.streams import preprocess as prep
    rng = np.random.default_rng(0)
    n, d = 4096, 64
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    st = prep.norm_init(d)
    fn = jax.jit(lambda s, xx: prep.norm_impute_fused(s, xx))
    us = _timeit(fn, st, x, nbytes=n * d * 4)
    rows.append(("kernel_norm_fused_4096x64", us,
                 f"{n / us * 1e6:.0f} events/s"))

    hids = jnp.asarray(rng.integers(0, 1 << 20, (2048, 8)), jnp.int32)
    hvals = jnp.asarray(rng.normal(size=(2048, 8)), jnp.float32)
    fn = jax.jit(lambda i, v: prep.hash_features(i, v, 256))
    us = _timeit(fn, hids, hvals, nbytes=2048 * 8 * 8)
    rows.append(("kernel_hash_features_2048x8", us,
                 f"{2048 / us * 1e6:.0f} events/s"))

    g = jnp.asarray(rng.normal(size=(65536,)), jnp.float32)
    r0 = comp.ef_init(g)
    fn = jax.jit(comp.ef_roundtrip)
    us = _timeit(fn, r0, g, nbytes=65536 * 4)
    rows.append(("kernel_ef_int8_64k", us,
                 f"{65536 * 4 / us:.0f} MB/s"))
    fn = jax.jit(lambda r, xx: comp.ef_topk_int8_roundtrip(r, xx, 6554))
    us = _timeit(fn, r0, g, nbytes=65536 * 4)
    rows.append(("kernel_ef_topk_int8_64k", us,
                 f"{65536 * 4 / us:.0f} MB/s k=10%"))


def bench_pipeline_fuse_xla(rows, quick):
    """The fuse="xla" segment mode vs the default per-op jit: one row per
    mode on the same cut so the trajectory tracks the fusion win (the
    number quoted in standard_stream_pipeline's docstring)."""
    from repro.core.pipeline import standard_stream_pipeline
    x = jnp.asarray(np.random.default_rng(0).normal(size=(256, 16)),
                    jnp.float32)
    y = jnp.asarray(np.random.default_rng(1).integers(0, 2, 256), jnp.int32)
    stats = {}
    for mode in ("op", "xla"):
        pipe = standard_stream_pipeline(dim=16, sample_rate=0.5, fuse=mode)
        states = pipe.init_states()
        rng = jax.random.PRNGKey(0)

        def step(states, rng):
            states, out = pipe.run(states, {"x": x, "y": y, "rng": rng},
                                   pipe.names[:4])
            return states, out["rng"]

        states, rng = step(states, rng)       # compile
        stats[mode] = _timeit(lambda s, r: step(s, r)[1], states, rng,
                              iters=20, nbytes=256 * 16 * 4)
    rows.append(("pipeline_step_cut4_xla", stats["xla"],
                 f"{256 / stats['xla'] * 1e6:.0f} events/s, "
                 f"{stats['op'] / stats['xla']:.2f}x vs fuse=op"))


def bench_train_micro(rows, quick):
    """DL substrate: per-step wall time of a reduced-arch train step on CPU
    (sanity; real perf is the dry-run roofline in EXPERIMENTS.md)."""
    from repro.configs import get_config
    from repro.models import model_zoo as zoo
    from repro.train.optim import make_optimizer
    from repro.train.train_step import make_train_step
    cfg = get_config("qwen2-1.5b", smoke=True)
    params = zoo.init_params(cfg, 0)
    opt = make_optimizer(cfg, "adamw", lr=1e-3)
    state = opt.init(params)
    step_fn = jax.jit(make_train_step(cfg, opt, microbatches=1))
    batch = {"tokens": jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (4, 64)),
        jnp.int32)}
    us = _timeit(lambda p, s, st, b: step_fn(p, s, st, b),
                 params, state, jnp.asarray(0), batch, warmup=1, iters=3)
    toks = 4 * 64
    rows.append(("dl_train_step_smoke", us, f"{toks / us * 1e6:.0f} tok/s"))


def bench_serve_micro(rows, quick):
    from repro.configs import get_config
    from repro.models import model_zoo as zoo
    from repro.serve.engine import Request, ServeEngine
    cfg = get_config("qwen2-1.5b", smoke=True)
    params = zoo.init_params(cfg, 0)
    eng = ServeEngine(cfg, params, batch_size=2, max_len=64)
    reqs = [Request(i, np.arange(8) + i, max_new_tokens=8) for i in range(2)]
    t0 = time.perf_counter()
    eng.run(reqs)
    dt = time.perf_counter() - t0
    th = eng.throughput()
    rows.append(("serve_decode_smoke", dt * 1e6,
                 f"{th['decode_tok_per_s']:.0f} decode tok/s"))


def bench_serve_prefill_edge_decode(rows, quick):
    """DL-on-the-substrate path: the split serving graph (serve/ops) on
    a topology where the saturated cloud pod forces the KV cache over
    the downlink — the frontier DP must select cloud-prefill/edge-decode
    and price the decode op's (params + KV) state against edge mem_cap."""
    from repro.configs import get_config
    from repro.core import costmodel as cm
    from repro.core.placement import Objective, place_frontier
    from repro.models import model_zoo as zoo
    from repro.serve.engine import ServeEngine
    from repro.serve.ops import serving_graph
    cfg = get_config("qwen2-1.5b", smoke=True)
    eng = ServeEngine(cfg, zoo.init_params(cfg, 0), batch_size=2, max_len=32)
    g = serving_graph(eng, prompt_len=24, max_new_tokens=4)
    spec = cm.ClusterSpec(
        pools=[cm.Resource("edge0", "edge", chips=1, flops=4e9, mem_bw=5e9,
                           mem_cap=4e9, net_bw=1e9),
               cm.Resource("cloud0", "cloud", chips=1, flops=1e13,
                           mem_bw=2.5e9, mem_cap=64e9, net_bw=100e9)],
        links=[cm.Link("edge0", "cloud0", bw=1e9, latency=5e-3),
               cm.Link("cloud0", "edge0", bw=1e9, latency=5e-3)])
    obj = Objective()
    iters = 3 if quick else 10
    t0 = time.perf_counter()
    for _ in range(iters):
        plan, frontier = place_frontier(g, spec, 3e3, obj)
    us = (time.perf_counter() - t0) / iters * 1e6
    split = (plan.assignment.get("prefill") == "cloud0"
             and plan.assignment.get("decode") == "edge0")
    kv_state = next(c.state_bytes for c in g.costs() if c.name == "decode")
    rows.append(("serve_prefill_edge_decode", us,
                 f"split={split} feasible={plan.feasible} "
                 f"kv_state={kv_state / 1e3:.0f}KB "
                 f"lat={plan.latency_s * 1e3:.1f}ms"))


def bench_train_op_placed(rows, quick):
    """Train-as-an-Op path: a zoo train step wrapped as a pipeline Op
    (train/ops.dl_train_op) placed by the frontier DP — the roofline-
    declared cost anchors it on the pod (edge_capable=False, full
    params+opt state priced against mem_cap)."""
    from repro.configs import get_config
    from repro.core import costmodel as cm
    from repro.core.pipeline import OpGraph
    from repro.core.placement import Objective, place_frontier
    from repro.train.ops import dl_train_op
    from repro.train.optim import adamw
    cfg = get_config("qwen2-1.5b", smoke=True)
    op = dl_train_op(cfg, adamw(1e-3), batch_size=4, seq_len=64)
    g = OpGraph([op])
    spec = cm.ClusterSpec(pools=[cm.EDGE_NODE, cm.CLOUD_POD])
    obj = Objective()
    iters = 3 if quick else 10
    t0 = time.perf_counter()
    for _ in range(iters):
        plan, frontier = place_frontier(g, spec, 1e3, obj)
    us = (time.perf_counter() - t0) / iters * 1e6
    rows.append(("train_op_placed", us,
                 f"pool={plan.assignment.get(op.name)} "
                 f"state={op.cost.state_bytes / 1e6:.2f}MB "
                 f"flops/ev={op.cost.flops_per_event:.3g}"))


def bench_fleet(rows, quick):
    """Multi-tenant fleet control path (core/fleet): admission probes
    per second, one fleet-batched arbitration pass over triggered
    tenants, and a full 3-tenant orchestrated round (execute + arbitrate
    + apply) — the fleet layer's overhead on top of single-job control
    must stay bounded as tenants multiply."""
    from repro.core import costmodel as cm
    from repro.core.fleet import FleetOrchestrator, FleetScheduler, TenantSpec
    from repro.core.offload import OffloadController
    from repro.core.orchestrator import StreamJob
    from repro.core.pipeline import standard_stream_pipeline
    from repro.core.sla import SLA, pick_codec
    from repro.streams.generators import HyperplaneStream
    sla = SLA(max_latency_s=1e3, error_budget=11.0)
    spec = cm.ClusterSpec(pools=[cm.EDGE_NODE, cm.CLOUD_POD])

    def controller(cool=5):
        return OffloadController(
            standard_stream_pipeline(dim=8), spec,
            codec=pick_codec(sla).name, sla_spec=sla, cooldown=cool)

    # admission: probe + initial plan + ledger booking, per tenant
    n_admit = 4 if quick else 8
    t0 = time.perf_counter()
    sched = FleetScheduler(spec)
    for i in range(n_admit):
        r = sched.submit(TenantSpec(f"t{i}", sla=sla, demand_rate=1e4),
                         controller())
    us = (time.perf_counter() - t0) / n_admit * 1e6
    rows.append(("fleet_admit", us,
                 f"{len(sched.admitted)}/{n_admit} admitted, "
                 f"{1e6 / us:.0f} admissions/s"))

    # arbitration: every tenant triggers -> one batched pass replans all
    # in priority order against residual capacity
    sched2 = FleetScheduler(spec)
    for i in range(n_admit):
        sched2.submit(TenantSpec(f"t{i}", sla=sla, demand_rate=1e4,
                                 priority=i % 3), controller(cool=0))
    iters = 3 if quick else 6
    t0 = time.perf_counter()
    for step in range(1, iters + 1):
        rate = 5e4 if step % 2 else 1e4      # out-of-band every step
        sched2.arbitrate(step, {f"t{i}": rate for i in range(n_admit)})
    us = (time.perf_counter() - t0) / iters * 1e6
    grants = sum(1 for line in sched2.log if "grant" in line)
    rows.append(("fleet_arbitrate_replan", us,
                 f"{n_admit} tenants/pass, {grants} grants over "
                 f"{iters} passes, ledger ok={not sched2.ledger.check()}"))

    # full fleet round: 3 tenant jobs execute + one arbitration + apply
    fleet = FleetOrchestrator(spec)
    gens = {}
    for i in range(3):
        fleet.add_tenant(TenantSpec(f"job{i}", sla=sla, demand_rate=1e4),
                         StreamJob(f"job{i}", dim=8, sla=sla), seed=i)
        gens[f"job{i}"] = HyperplaneStream(dim=8, seed=10 + i, horizon=1e6)
    n_rounds = 3 if quick else 6
    step = [0]

    def round_():
        s = step[0]
        fleet.step_round({n: g.batch(s, 32) for n, g in gens.items()})
        step[0] += 1

    round_()                                  # compile warmup
    t0 = time.perf_counter()
    for _ in range(n_rounds):
        round_()
    us = (time.perf_counter() - t0) / n_rounds * 1e6
    ev = sum(m.events for m in fleet.finish().values())
    rows.append(("fleet_step_3tenants", us,
                 f"{ev} events, {3 * 32 / (us * 1e-6):.0f} ev/s fleet-wide, "
                 f"ledger ok={not fleet.scheduler.ledger.check()}"))


def bench_membership(rows, quick):
    """Dynamic-topology control path (core/membership): EWMA latency
    probe refresh (spec rewrite per sample), a pool join -> forced
    replan cycle, and the full silent pool-loss recovery (lease expiry
    -> involuntary checkpoint-rescale -> replan excluding the dead
    pool). Churn handling rides the per-step control path, so its cost
    must stay control-plane sized, not execute-sized."""
    from repro.core import costmodel as cm
    from repro.core.membership import MembershipDirectory
    from repro.core.orchestrator import Orchestrator, StreamJob
    from repro.core.pipeline import fanout_stream_graph
    from repro.core.sla import SLA

    sla = SLA(max_latency_s=1e3, error_budget=11.0)
    seed_spec = cm.ClusterSpec(
        pools=[cm.EDGE_NODE, cm.CLOUD_POD],
        links=[cm.Link("edge", "cloud", bw=2e6, latency=20e-3)])

    # probe refresh: one EWMA update + authoritative spec rewrite
    d = MembershipDirectory(seed_spec)
    n_probes = 200 if quick else 1000
    t0 = time.perf_counter()
    for i in range(n_probes):
        d.observe_latency("edge", "cloud", 20e-3 * (1.0 + (i % 7) * 0.01),
                          now=i)
    us = (time.perf_counter() - t0) / n_probes * 1e6
    rows.append(("latency_probe_refresh", us,
                 f"{n_probes} probes -> v{d.version}, "
                 f"{1e6 / us:.0f} probes/s"))

    pool = cm.Resource("edge_b", "edge", chips=2, flops=4e12, mem_bw=100e9,
                       mem_cap=8e9, net_bw=1e9, net_latency=5e-3)
    link = cm.Link("edge_b", "cloud", bw=8e6, latency=5e-3)

    def live_orchestrator():
        dd = MembershipDirectory(seed_spec)
        orch = Orchestrator(StreamJob("m", dim=8, sla=sla, membership=dd,
                                      pipeline=fanout_stream_graph(8)))
        orch.begin(1e4, seed=0)
        return dd, orch

    iters = 3 if quick else 6

    # join -> event drain -> forced replan onto the new pool
    dd, orch = live_orchestrator()
    t0 = time.perf_counter()
    for i in range(iters):
        step = 2 * i
        dd.register(pool, links=[link], now=step, monitored=False)
        orch.topology_step(step, 1e4)
        assert "edge_b" in set(orch._exec_assignment.values())
        dd.deregister("edge_b", now=step + 1)     # reset (drained below)
        orch.topology_step(step + 1, 1e4)
    us = (time.perf_counter() - t0) / (2 * iters) * 1e6
    joins = sum(1 for ln in orch.metrics.decisions if ":pool_joined" in ln)
    rows.append(("membership_join_replan", us,
                 f"{iters} join/leave cycles, {joins} forced replans"))

    # silent loss: heartbeats stop -> lease expiry inside the step's
    # tick -> involuntary recover + replan excluding the dead pool
    dd, orch = live_orchestrator()
    now = [0]
    t0 = time.perf_counter()
    for _ in range(iters):
        s = now[0]
        dd.register(pool, links=[link], now=s)    # monitored: leased
        orch.topology_step(s, 1e4)
        dead = s + dd.lease_ticks + 1             # silence past the lease
        orch.topology_step(dead, 1e4)
        assert "edge_b" not in orch.controller.resources.pools
        now[0] = dead + 1
    us = (time.perf_counter() - t0) / iters * 1e6
    rows.append(("membership_pool_loss_recover", us,
                 f"{iters} lease-expiry recoveries, "
                 f"rescales={orch.elastic.rescales}"))


ALL_BENCHES = [bench_s1_throughput_scaling, bench_s2_update_latency,
               bench_s3_offload, bench_pipeline_partition,
               bench_pipeline_fuse_xla,
               bench_dag_placement, bench_dag_place_multipool,
               bench_dag_place_dp,
               bench_adaptive_codec_replan, bench_uplink_codec,
               bench_fusion_join, bench_fleet, bench_membership,
               bench_serve_prefill_edge_decode, bench_train_op_placed,
               bench_s4_feature_matrix, bench_generators, bench_sketches,
               bench_kernel_dispatch,
               bench_train_micro, bench_serve_micro]

# fast perf-path subset for CI (--smoke): skips the DL train/serve micro
# rows (their substrate is already compiled by the test suite); like a
# full run, it exits non-zero on any ERROR row
SMOKE_BENCHES = [bench_s1_throughput_scaling, bench_s2_update_latency,
                 bench_s3_offload, bench_pipeline_partition,
                 bench_pipeline_fuse_xla,
                 bench_dag_placement, bench_dag_place_multipool,
                 bench_dag_place_dp,
                 bench_adaptive_codec_replan, bench_uplink_codec,
                 bench_fusion_join, bench_fleet, bench_membership,
                 bench_serve_prefill_edge_decode, bench_train_op_placed,
                 bench_s4_feature_matrix, bench_generators, bench_sketches,
                 bench_kernel_dispatch]


def _git_sha() -> str:
    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(os.path.abspath(__file__)))
        return out.stdout.strip() or "unknown"
    except Exception:
        return "unknown"


def rows_to_json(rows) -> dict:
    """The persisted trajectory snapshot (``BENCH_<pr>.json``): schema tag,
    provenance (sha, backend, jax version — the things that explain a perf
    shift), and one object per row. No timestamp: re-running at the same
    sha must produce a diff only in the timing fields."""
    out = []
    for name, us, derived in rows:
        stat = us if isinstance(us, BenchStat) else BenchStat(float(us))
        out.append({"name": name,
                    "median_us": round(float(stat), 3),
                    "p90_us": round(stat.p90_us, 3),
                    "iters": stat.iters,
                    "units": str(derived),
                    "bytes": stat.nbytes})
    return {"schema": BENCH_SCHEMA,
            "git_sha": _git_sha(),
            "backend": jax.default_backend(),
            "jax_version": jax.__version__,
            "rows": out}


def main(argv=None) -> int:
    import sys

    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="fast subset (CI)")
    ap.add_argument("--only", metavar="SUBSTR", default=None,
                    help="run only bench functions whose name contains "
                         "SUBSTR (e.g. --only sketch)")
    ap.add_argument("--out", metavar="PATH", default=None,
                    help="write the machine-readable BENCH_*.json snapshot "
                         "(the perf-trajectory format compare.py gates on)")
    args, _ = ap.parse_known_args(argv)
    quick = args.quick or args.smoke
    benches = SMOKE_BENCHES if args.smoke else ALL_BENCHES
    if args.only:
        benches = [b for b in benches if args.only in b.__name__]
    rows = []
    for bench in benches:
        try:
            bench(rows, quick)
        except Exception as e:  # report every bench, then fail below
            rows.append((bench.__name__, -1.0, f"ERROR {type(e).__name__}: {e}"))
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.2f},{derived}")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rows_to_json(rows), f, indent=1)
            f.write("\n")
        print(f"wrote {len(rows)} rows -> {args.out}", file=sys.stderr)
    errors = [r for r in rows if str(r[2]).startswith("ERROR")]
    if errors:
        print(f"FAILED: {len(errors)} benchmark(s) errored", file=sys.stderr)
        return 1
    return 0


if __name__ == '__main__':
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())
