"""DL serving on the pipeline substrate: cloud-prefill/edge-decode with
the KV cache as codec-governed uplink state (the PR-10 tentpole).

``serve/ops`` decomposes the ServeEngine into a two-op graph
``prefill -> decode`` whose single flow edge IS the KV-cache hop:

* the decode op's ``state_bytes`` (weights + live KV cache) is priced by
  the placement DP against each pool's ``mem_cap`` — an edge pool too
  small for the cache is provably excluded;
* decode declares ``OperatorCost.downlink_ok``, so ``{decode}`` is a
  legal frontier: prefill runs on the pod, the cache ships *down* the
  priced link, and decode runs at the edge — the split a saturated pod
  forces;
* executing the graph at that frontier goes through the engine's own
  jitted executables, so under the identity codec the output is bitwise
  identical to ``ServeEngine.run``;
* the KV codec ladder (``identity -> kv_int8 -> kv_latent``) plugs into
  the same SLA admission + offload-controller escalation loop the
  gradient codecs use: a saturating decode ramp escalates KV-cache
  compression, and recovery de-escalates back toward lossless.

  PYTHONPATH=src python examples/edge_serving.py
"""

import numpy as np

from repro.configs import get_config
from repro.core import costmodel as cm
from repro.core.codecs import KV_CODECS
from repro.core.offload import OffloadController
from repro.core.placement import Objective, place_frontier
from repro.core.sla import SLA
from repro.models import model_zoo as zoo
from repro.serve.engine import Request, ServeEngine
from repro.serve.ops import serve_wave_batch, serving_graph
from repro.train.ops import dl_train_op
from repro.train.optim import adamw


def build_cluster(edge_mem: float = 4e9,
                  kv_link_bw: float = 2e7) -> cm.ClusterSpec:
    """One modest edge box and one *narrow* cloud pod: the pod's memory
    bandwidth saturates when it holds both serving phases at high rate,
    which is exactly what pushes decode out to the edge."""
    edge = cm.Resource("edge0", "edge", chips=1, flops=4e9, mem_bw=5e9,
                       mem_cap=edge_mem, net_bw=1e9)
    cloud = cm.Resource("cloud0", "cloud", chips=1, flops=1e13,
                        mem_bw=2.5e9, mem_cap=64e9, net_bw=100e9)
    return cm.ClusterSpec(
        pools=[edge, cloud],
        links=[cm.Link("edge0", "cloud0", bw=1e9, latency=5e-3),
               cm.Link("cloud0", "edge0", bw=kv_link_bw, latency=5e-3)])


def main():
    cfg = get_config("qwen2-1.5b", smoke=True)
    params = zoo.init_params(cfg, 0)
    engine = ServeEngine(cfg, params, batch_size=2, max_len=32)
    graph = serving_graph(engine, prompt_len=24, max_new_tokens=4)
    print("== serving as a split op graph ==")
    for c in graph.costs():
        print(f"  {c.name:8s} flops/ev={c.flops_per_event:10.3g} "
              f"state={c.state_bytes / 1e3:7.1f}KB "
              f"downlink_ok={c.downlink_ok}")
    print(f"  frontiers: {sorted(sorted(f) for f in graph.frontiers())}")

    # -- placement: mem_cap exclusion and the forced split ----------------
    obj = Objective()
    print("\n== placement DP prices KV state against mem_cap ==")
    tiny = build_cluster(edge_mem=1e3)       # KV cache cannot fit
    plan, _ = place_frontier(graph, tiny, 1e3, obj)
    print(f"  edge mem 1KB  -> {plan.assignment} (edge excluded)")
    assert plan.assignment == {"prefill": "cloud0", "decode": "cloud0"}
    roomy = build_cluster()
    plan, frontier = place_frontier(graph, roomy, 3e3, obj)
    print(f"  edge mem 4GB  -> {plan.assignment} at 3000 ev/s "
          f"(pod saturated: cloud-prefill/edge-decode)")
    assert plan.assignment == {"prefill": "cloud0", "decode": "edge0"}
    assert frontier == frozenset({"decode"})

    # -- execution parity: the graph run IS the engine --------------------
    print("\n== graph execution at {decode} vs ServeEngine: bitwise ==")
    prompts = [np.arange(1, 9, dtype=np.int32) + i for i in range(2)]
    ref_eng = ServeEngine(cfg, params, batch_size=2, max_len=32, seed=0)
    reqs = [Request(i, p, max_new_tokens=4) for i, p in enumerate(prompts)]
    ref_eng.run(reqs)
    ref = np.array([r.out_tokens for r in reqs])
    states = graph.init_states()
    batch = serve_wave_batch(engine, prompts, seed=0)
    states, out = graph.run(states, batch, frontier)
    got = np.asarray(out["out_tokens"])
    assert np.array_equal(ref, got), (ref, got)
    print(f"  out_tokens match: {got.tolist()}")

    # -- the KV codec ladder ----------------------------------------------
    print("\n== KV codec ladder (per-payload bound, stateless) ==")
    for c in KV_CODECS:
        print(f"  {c.name:10s} wire ratio={c.ratio:.3f} "
              f"error bound={c.error_bound:.4f}")

    # -- saturating decode ramp: SLA-governed KV compression --------------
    # The serving SLA (a latency target + an error budget wide enough to
    # admit the lossy KV codecs) drives the controller's escalate/
    # de-escalate loop: as the offered rate ramps, the plan migrates to
    # cloud-prefill/edge-decode, the KV downlink saturates, and codec
    # re-admission escalates the cache compression; the migration itself
    # is priced (state bytes over the old->new link).
    print("\n== saturating decode ramp: KV codec escalation ==")
    sla = SLA(max_latency_s=0.5, error_budget=0.8)
    ctl = OffloadController(
        graph, roomy, obj, codec="identity",
        sla_spec=sla, codec_candidates=[c.name for c in KV_CODECS],
        cooldown=1, codec_cooldown=2)
    ramp = [1e3] * 3 + [1.8e3, 2.4e3, 3.2e3] + [3.2e3] * 3 + [1e3] * 4
    ctl.initial_plan(ramp[0])
    for step, rate in enumerate(ramp):
        d = ctl.observe(step, rate)
        if d.reason != "hold":
            mig = (f" moved={len(d.migration.moves)} ops "
                   f"({d.migration.bytes / 1e3:.0f}KB, "
                   f"{d.migration.seconds * 1e3:.1f}ms)"
                   if d.migration.moves else "")
            print(f"  step {step:2d}: rate={rate:6.0f} -> {d.reason:9s} "
                  f"codec={d.codec:10s} "
                  f"edge={sorted(d.frontier) or ['-']}{mig}")
    traj = [d.codec for d in ctl.history]
    compact = [traj[0]] + [b for a, b in zip(traj, traj[1:]) if a != b]
    print(f"  codec trajectory: {' -> '.join(compact)}")
    assert any(c != "identity" for c in traj), \
        "the saturating ramp must escalate the KV codec at least once"

    # -- train as an Op: same substrate, same DP --------------------------
    print("\n== train step as a placement-priced op ==")
    top = dl_train_op(cfg, adamw(1e-3), batch_size=4, seq_len=64)
    from repro.core.pipeline import OpGraph
    tplan, _ = place_frontier(OpGraph([top]), roomy, 1e3, obj)
    print(f"  {top.name}: state={top.cost.state_bytes / 1e6:.2f}MB "
          f"edge_capable={top.cost.edge_capable} "
          f"-> {tplan.assignment}")
    assert tplan.assignment[top.name] == "cloud0"

    print("\nOK")


if __name__ == "__main__":
    main()
