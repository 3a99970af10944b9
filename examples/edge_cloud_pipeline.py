"""Multi-pool placement over a ClusterSpec topology + SLA-driven uplink
codecs + dynamic offloading under a traffic burst (S2CE O2, S3) — plus
the straggler-tolerant feeder.

The cluster is declared as a first-class topology: named Resource pools
(here 2 edge pools + 2 cloud pods) and explicit directed Links carrying
bandwidth, latency, and an uplink codec. ``place_frontier`` assigns each
side of a downward-closed frontier cut across *all* pools of its kind,
pricing every crossing link with codec-compressed bytes and DAG latency
as the critical path.

The final section demonstrates **rate-adaptive codec control**: the
offload controller re-runs codec admission on every replan from
windowed SLA telemetry, escalating the uplink codec when the link
saturates and de-escalating toward lossless on recovery — printing the
codec trajectory of a saturating rate ramp.

  PYTHONPATH=src python examples/edge_cloud_pipeline.py
"""

from repro.core import costmodel as cm
from repro.core import pipeline as pl
from repro.core.offload import OffloadController
from repro.core.placement import (Objective, place_frontier,
                                  place_graph_exhaustive)
from repro.core.sla import SLA, SLATracker, pick_codec
from repro.streams.feeder import StreamFeeder
from repro.streams.generators import HyperplaneStream


def build_cluster(codec: str = "identity") -> cm.ClusterSpec:
    """A 2-edge-pool + 2-cloud-pod topology with per-link codecs:
    a gateway-class edge pool, a weaker far-edge pool, the main pod, and
    a smaller regional pod."""
    far_edge = cm.Resource("far_edge", "edge", chips=1, flops=1e12,
                           mem_bw=40e9, mem_cap=2e9, net_bw=0.5e9,
                           net_latency=35e-3, energy_w=10.0)
    regional = cm.Resource("regional", "cloud", chips=64,
                           net_latency=0.5e-3, energy_w=220.0)
    return cm.ClusterSpec(
        pools=[cm.EDGE_NODE, far_edge, cm.CLOUD_POD, regional],
        links=[
            cm.Link("edge", "cloud", bw=1e9, latency=20e-3, codec=codec),
            cm.Link("edge", "regional", bw=0.8e9, latency=15e-3,
                    codec=codec),
            cm.Link("far_edge", "cloud", bw=0.5e9, latency=35e-3,
                    codec=codec),
            cm.Link("far_edge", "regional", bw=0.5e9, latency=30e-3,
                    codec=codec),
            cm.Link("edge", "far_edge", bw=2e9, latency=5e-3),
        ])


def main():
    # -- SLA-driven codec admission ---------------------------------------
    print("== SLA error budget -> cheapest admissible uplink codec ==")
    for budget in (0.0, 0.1, 11.0):
        c = pick_codec(SLA(error_budget=budget))
        print(f"  budget {budget:5.2f} -> {c.name:13s} "
              f"(wire ratio {c.ratio:.3f}, tested bound {c.error_bound:.4f})")
    codec = pick_codec(SLA(error_budget=11.0))

    # -- multi-pool frontier placement across ingest rates ----------------
    cluster = build_cluster(codec.name)
    print(f"\n== {cluster} ==")
    g = pl.fanout_stream_graph(dim=16)
    print("== multi-pool frontier placement across ingest rates ==")
    for rate in (1e3, 1e5, 1e6):
        plan, frontier = place_frontier(g, cluster, rate,
                                        Objective(energy_weight=0.1))
        oracle = place_graph_exhaustive(g, cluster, rate,
                                        Objective(energy_weight=0.1))
        obj = Objective(energy_weight=0.1)
        pools_used = sorted(set(plan.assignment.values()))
        print(f"rate {rate:9.0f} ev/s -> edge={sorted(frontier) or ['-']}")
        print(f"    pools={pools_used} latency={plan.latency_s*1e3:6.2f}ms "
              f"uplink={plan.uplink_utilization:6.4f} "
              f"feasible={plan.feasible} "
              f"oracle_match={obj.score(plan) <= obj.score(oracle)*1.0001}")

    # -- dynamic offload under a 40x burst, multi-pool plan identity ------
    print("\n== dynamic offload under a 40x burst ==")
    ctl = OffloadController(g, cluster, cooldown=2, codec=codec.name)
    sla = SLATracker(SLA(max_latency_s=0.05))
    ctl.initial_plan(5e3)
    rates = [5e3] * 10 + [2e5] * 10 + [5e3] * 10      # burst in the middle
    for step, rate in enumerate(rates):
        d = ctl.observe(step, rate, sla)
        if d.reason != "hold":
            print(f"step {step:3d}: rate={rate:9.0f} -> {d.reason:9s} "
                  f"edge={sorted(d.frontier) or ['-']} codec={d.codec}")
    print(f"total migrations: {ctl.migrations()}")

    # -- rate-adaptive codec control: re-admission at replan time ---------
    # The uplink codec is a runtime control dimension: on every replan
    # the controller re-runs codec admission against the windowed SLA
    # report + the modeled saturation of the incumbent plan, escalating
    # to cheaper wire when the uplink saturates and de-escalating toward
    # lossless when the link has headroom (hysteresis band + cooldown
    # stop codec flapping). Links here declare no codec, so the blanket
    # candidate actually gets to move.
    print("\n== rate-adaptive uplink codec under a saturating rate ramp ==")
    pipe = pl.standard_stream_pipeline(dim=8, sample_rate=0.5)
    adaptive_sla = SLA(max_latency_s=1e3, error_budget=11.0)
    # a rate-aware initial pick: with no bandwidth pressure the most
    # faithful admissible codec wins (lossless), unlike the static
    # cheapest-wire admission above
    start = pick_codec(adaptive_sla, report={"uplink_utilization": 0.0,
                                             "violation_rate": 0.0})
    actl = OffloadController(
        pipe, cm.ClusterSpec.edge_cloud(), codec=start.name,
        sla_spec=adaptive_sla, cooldown=2, codec_cooldown=4)
    ramp = [1e4] * 6 + [8e7] * 6 + [1e4] * 6       # saturate, then recover
    actl.initial_plan(ramp[0])
    for step, rate in enumerate(ramp):
        d = actl.observe(step, rate)
        if d.reason != "hold":
            print(f"step {step:3d}: rate={rate:9.0f} -> {d.reason:9s} "
                  f"codec={d.codec:13s} "
                  f"uplink={d.plan.uplink_utilization:6.3f} "
                  f"edge={sorted(d.frontier) or ['-']}")
    traj = [d.codec for d in actl.history]
    compact = [traj[0]] + [b for a, b in zip(traj, traj[1:]) if a != b]
    print(f"codec trajectory: {' -> '.join(compact)}")
    assert len(compact) >= 3, "ramp should escalate and de-escalate"

    print("\n== straggler-tolerant feeding ==")
    def make(shard, idx, n):
        return HyperplaneStream(dim=8, seed=shard).batch(idx, n)
    feeder = StreamFeeder(
        make, n_shards=4, batch_per_shard=256, deadline_s=0.05,
        inject_straggle=lambda s, i: 0.2 if (s == 2 and i % 3 == 1) else 0.0)
    feeder.start()
    for _ in range(6):
        b = feeder.next()
    feeder.stop()
    print(f"batches={feeder.stats.batches} "
          f"straggler_rescues={feeder.stats.straggler_rescues} "
          f"(deterministic replay, no data loss)")
    assert feeder.stats.straggler_rescues >= 1
    print("\nOK")


if __name__ == "__main__":
    main()
