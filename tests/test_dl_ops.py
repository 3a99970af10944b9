"""The DL stack on the pipeline substrate (serve/ops, train/ops): the
split serving graph and the wrapped train step must be bitwise-identical
to the standalone engine/train-step calls under the identity codec; the
placement DP must price KV-cache ``state_bytes`` against ``mem_cap``
(provable edge exclusion) and select cloud-prefill/edge-decode when the
pod saturates; the KV codecs must honor their tested error bounds; and
replans must carry the priced migration of resident op state."""

from dataclasses import replace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import get_config
from repro.core import costmodel as cm
from repro.core.codecs import (DEFAULT_CODECS, KV_CODECS, get_codec,
                               kv_latent_codec)
from repro.core.offload import OffloadController
from repro.core.pipeline import OpGraph
from repro.core.placement import (Objective, _graph_plan, place_frontier,
                                  place_frontier_oracle)
from repro.launch.roofline import dl_operator_cost
from repro.models import model_zoo as zoo
from repro.serve.engine import Request, ServeEngine
from repro.serve.ops import (decode_op, kv_cache_bytes, param_bytes,
                             prefill_op, serve_wave_batch, serving_graph)
from repro.serve.sampling import SamplingParams
from repro.train.ops import dl_train_op, train_state_bytes
from repro.train.optim import adamw
from repro.train.train_step import make_train_step

CFG = get_config("qwen2-1.5b", smoke=True)
PARAMS = zoo.init_params(CFG, 0)
PROMPTS = [np.arange(1, 7, dtype=np.int32), np.arange(3, 11, dtype=np.int32)]


def make_engine(**kw):
    kw = {"batch_size": 2, "max_len": 32, "seed": 0, **kw}
    return ServeEngine(CFG, PARAMS, **kw)


def engine_reference(sampling=SamplingParams(greedy=True), new_tokens=5):
    eng = make_engine(sampling=sampling)
    reqs = [Request(i, p, max_new_tokens=new_tokens)
            for i, p in enumerate(PROMPTS)]
    eng.run(reqs)
    return np.array([r.out_tokens for r in reqs])


def graph_run(frontier, sampling=SamplingParams(greedy=True), new_tokens=5,
              uplink=None):
    eng = make_engine(sampling=sampling)
    g = serving_graph(eng, prompt_len=8, max_new_tokens=new_tokens)
    states = g.init_states()
    batch = serve_wave_batch(eng, PROMPTS, seed=0)
    states, out = g.run(states, batch, frozenset(frontier), uplink=uplink)
    return g, np.asarray(out["out_tokens"])


# ---------------------------------------------------------------------------
# differential contract: the graph path IS the engine
# ---------------------------------------------------------------------------

def test_serving_graph_bitwise_vs_engine_greedy():
    ref = engine_reference()
    for frontier in ((), ("prefill",), ("decode",), ("prefill", "decode")):
        _, got = graph_run(frontier)
        assert np.array_equal(ref, got), (frontier, ref, got)


def test_serving_graph_bitwise_vs_engine_sampled():
    """Non-greedy sampling pins the rng threading: the prefill op must
    split the wave key exactly like ``_serve_wave`` and the decode loop
    must hand the engine's jitted step the same keys in the same order."""
    sp = SamplingParams(temperature=0.8, top_k=8)
    ref = engine_reference(sampling=sp)
    _, got = graph_run(("decode",), sampling=sp)
    assert np.array_equal(ref, got)


def test_prefill_op_emits_the_engine_cache_pytree():
    eng = make_engine()
    op = prefill_op(eng, prompt_len=8)
    batch = serve_wave_batch(eng, PROMPTS, seed=0)
    _, out = op.fn(None, batch)
    want = jax.eval_shape(lambda: zoo.init_caches(CFG, 2, 32))
    got_td = jax.tree_util.tree_structure(out["kv"])
    assert got_td == jax.tree_util.tree_structure(want)
    assert out["tok"].shape == (2,)


def test_interleaved_run_applies_wire_on_every_side_change():
    """A non-strict frontier executes as same-side runs in list order,
    with the wire transform applied at each crossing: ``{decode}`` is
    source(edge) -> prefill(cloud) -> decode(edge), two crossings, while
    the strictly-closed ``{prefill}`` keeps the single legacy uplink."""
    calls = []

    def wire(env):
        calls.append(sorted(env))
        return env

    _, got = graph_run(("decode",), uplink=wire)
    assert len(calls) == 2
    # the second crossing carries the KV cache down to the edge decode
    assert "kv" in calls[1]
    calls.clear()
    _, got2 = graph_run(("prefill",), uplink=wire)
    assert len(calls) == 1
    assert np.array_equal(got, got2)


def test_train_op_bitwise_vs_standalone_jitted():
    opt = adamw(1e-3)
    tokens = np.random.RandomState(0).randint(
        1, CFG.vocab_size, (2, 16)).astype(np.int32)
    step_fn = jax.jit(make_train_step(CFG, opt, impl="chunked",
                                      clip_norm=1.0))
    p, o, s = PARAMS, opt.init(PARAMS), jnp.zeros((), jnp.int32)
    ref_losses = []
    for _ in range(2):
        p, o, s, m = step_fn(p, o, s, {"tokens": jnp.asarray(tokens)})
        ref_losses.append(np.asarray(m["loss"]))

    op = dl_train_op(CFG, opt, batch_size=2, seq_len=16)
    g = OpGraph([op])
    states = g.init_states()
    batch = {"tokens": jnp.asarray(tokens), "rng": jax.random.PRNGKey(0)}
    for i in range(2):
        states, out = g.run(states, batch, frozenset())
        assert np.array_equal(ref_losses[i], np.asarray(out["loss"]))
    pw, ow, sw = states[op.name]
    for a, b in zip(jax.tree_util.tree_leaves(p),
                    jax.tree_util.tree_leaves(pw)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert int(sw) == 2


# ---------------------------------------------------------------------------
# roofline-declared costs
# ---------------------------------------------------------------------------

def test_dl_operator_cost_roofline_rules():
    n = CFG.param_counts()["active"]
    pb = param_bytes(CFG)
    tr = dl_operator_cost("t", CFG, phase="train", batch=4, seq_len=64,
                          param_bytes=pb)
    assert tr.flops_per_event == pytest.approx(6.0 * n * 64)
    assert tr.bytes_per_event == pytest.approx(3.0 * pb / 4)
    pf = dl_operator_cost("p", CFG, phase="prefill", batch=2, seq_len=24,
                          param_bytes=pb)
    assert pf.flops_per_event == pytest.approx(2.0 * n * 24)
    de = dl_operator_cost("d", CFG, phase="decode", batch=2, seq_len=0,
                          new_tokens=4, param_bytes=pb, downlink_ok=True)
    assert de.flops_per_event == pytest.approx(2.0 * n * 4)
    # decode re-streams the weights once per generated token
    assert de.bytes_per_event == pytest.approx(pb * 4 / 2)
    assert de.downlink_ok and not tr.downlink_ok
    with pytest.raises(ValueError):
        dl_operator_cost("x", CFG, phase="nope", batch=1, seq_len=1)


def test_train_state_bytes_counts_params_and_moments():
    opt = adamw(1e-3)
    pb = param_bytes(CFG)
    sb = train_state_bytes(CFG, opt)
    assert sb >= 2 * pb          # params + at least adam's m/v


def test_set_measured_costs_preserves_downlink_ok():
    eng = make_engine()
    g = serving_graph(eng, prompt_len=8, max_new_tokens=4)
    flat = replace(g.op("decode").cost, downlink_ok=False,
                   flops_per_event=123.0)
    g.set_measured_costs({"decode": flat})
    c = {x.name: x for x in g.costs()}["decode"]
    assert c.flops_per_event == 123.0 and c.downlink_ok


# ---------------------------------------------------------------------------
# placement: KV state priced against mem_cap, downlink split selected
# ---------------------------------------------------------------------------

def serving_spec(edge_mem=4e9, edge_flops=4e9, cloud_membw=2.5e9,
                 down_bw=1e9):
    edge = cm.Resource("edge0", "edge", chips=1, flops=edge_flops,
                       mem_bw=5e9, mem_cap=edge_mem, net_bw=1e9)
    cloud = cm.Resource("cloud0", "cloud", chips=1, flops=1e13,
                        mem_bw=cloud_membw, mem_cap=64e9, net_bw=100e9)
    return cm.ClusterSpec(
        pools=[edge, cloud],
        links=[cm.Link("edge0", "cloud0", bw=1e9, latency=5e-3),
               cm.Link("cloud0", "edge0", bw=down_bw, latency=5e-3)])


def serving_graph_for_placement():
    eng = make_engine()
    return serving_graph(eng, prompt_len=24, max_new_tokens=4)


def test_dp_excludes_edge_pool_with_insufficient_mem_cap():
    g = serving_graph_for_placement()
    kv_state = g.op("decode").cost.state_bytes
    tiny = serving_spec(edge_mem=kv_state / 2)
    assert kv_state > tiny.pools["edge0"].mem_cap
    plan, frontier = place_frontier(g, tiny, 1e3, Objective())
    assert plan.feasible
    assert plan.assignment == {"prefill": "cloud0", "decode": "cloud0"}
    assert frontier == frozenset()
    # the exclusion is the evaluator's, not a DP artifact
    p = _graph_plan(g, {"prefill": "cloud0", "decode": "edge0"}, tiny, 1e3)
    assert not p.feasible and any("memory" in n for n in p.notes)


def test_dp_selects_cloud_prefill_edge_decode_under_pod_saturation():
    """At 3k waves/s the narrow pod cannot hold both phases and the weak
    edge cannot hold prefill: the only feasible plan ships the KV cache
    down the priced link — and the DP finds it (enumeration agrees)."""
    g = serving_graph_for_placement()
    spec = serving_spec()
    obj = Objective()
    for place in (place_frontier, place_frontier_oracle):
        plan, frontier = place(g, spec, 3e3, obj)
        assert plan.feasible, place.__name__
        assert plan.assignment == {"prefill": "cloud0", "decode": "edge0"}
        assert frontier == frozenset({"decode"})
    # the KV crossing is priced on the downlink, not free
    assert plan.link_utilization[("cloud0", "edge0")] > 0.0


def test_downlink_requires_the_consumer_flag():
    """Without ``downlink_ok`` the same cloud->edge crossing is backhaul:
    the relaxation is per-consumer, not a blanket rule change."""
    g = serving_graph_for_placement()
    spec = serving_spec()
    split = {"prefill": "cloud0", "decode": "edge0"}
    assert _graph_plan(g, split, spec, 1e3).feasible
    stripped = OpGraph([
        replace(g.op("prefill"), cost=g.op("prefill").cost),
        replace(g.op("decode"),
                cost=replace(g.op("decode").cost, downlink_ok=False)),
    ])
    p = _graph_plan(stripped, split, spec, 1e3)
    assert not p.feasible
    assert any("backhaul" in n for n in p.notes)
    # and {decode} is no longer a frontier of the stripped graph
    fs = {frozenset(f) for f in stripped.frontiers()}
    assert frozenset({"decode"}) not in fs
    assert frozenset({"decode"}) in {frozenset(f) for f in g.frontiers()}


# ---------------------------------------------------------------------------
# KV codecs: tested error bounds, parametrized ladder
# ---------------------------------------------------------------------------

def _kv_leaves():
    caches = zoo.init_caches(CFG, 2, 32)
    eng = make_engine()
    batch = serve_wave_batch(eng, PROMPTS, seed=0)
    _, caches = eng._prefill(eng.params, {"tokens": batch["tokens"]})
    return [l for l in jax.tree_util.tree_leaves(caches)
            if jnp.issubdtype(jnp.result_type(l), jnp.floating)
            and l.ndim > 0]


def test_kv_int8_bound_on_gaussian_and_real_kv():
    codec = get_codec("kv_int8")
    rng = np.random.default_rng(0)
    payloads = [jnp.asarray(rng.normal(size=(64, 32)).astype(np.float32))]
    payloads += _kv_leaves()
    assert payloads[-1].size > 0
    for x in payloads:
        dec, _ = codec.roundtrip(codec.init_residual(x), x)
        scale = max(float(jnp.max(jnp.abs(x))), 1e-30)
        err = float(jnp.max(jnp.abs(dec - x))) / scale
        assert err <= codec.error_bound * 1.001, err


def test_kv_latent_bound_on_gaussian():
    """The latent codec's bound is distributional (energy outside the
    retained subspace): relative L2 error on generic payloads must stay
    within sqrt(1 - r_frac) + int8 quantum, with margin."""
    rng = np.random.default_rng(1)
    for r_frac in (0.5, 0.25):
        codec = kv_latent_codec(r_frac)
        x = jnp.asarray(rng.normal(size=(128, 64)).astype(np.float32))
        dec, _ = codec.roundtrip(codec.init_residual(x), x)
        rel = float(jnp.linalg.norm(dec - x) / jnp.linalg.norm(x))
        assert rel <= codec.error_bound * 1.05, (r_frac, rel)
        # identity subspace: r_frac=1 keeps everything but the quantum
    full = kv_latent_codec(1.0)
    x = jnp.asarray(rng.normal(size=(32, 16)).astype(np.float32))
    dec, _ = full.roundtrip(full.init_residual(x), x)
    rel = float(jnp.linalg.norm(dec - x) / jnp.linalg.norm(x))
    assert rel <= 0.02


def test_kv_latent_roundtrip_on_real_kv_leaves():
    codec = get_codec("kv_latent")
    for x in _kv_leaves():
        dec, _ = codec.roundtrip(codec.init_residual(x), x)
        assert dec.shape == x.shape
        nx = float(jnp.linalg.norm(x))
        if nx > 0:
            rel = float(jnp.linalg.norm(dec - x)) / nx
            assert rel <= codec.error_bound * 1.05


def test_kv_codec_registry_and_ladder():
    assert [c.name for c in KV_CODECS] == ["identity", "kv_int8",
                                           "kv_latent"]
    # the serving ladder does NOT leak into the gradient default ladder
    assert not any(c.name.startswith("kv_") for c in DEFAULT_CODECS)
    c = get_codec("kv_latent_r0.25")
    assert c.ratio == pytest.approx(0.25 * 0.25)
    assert c.error_bound == pytest.approx((1 - 0.25) ** 0.5 + 1 / 127)
    with pytest.raises(ValueError):
        kv_latent_codec(0.0)
    with pytest.raises(KeyError):
        get_codec("kv_nope")


# ---------------------------------------------------------------------------
# migration pricing
# ---------------------------------------------------------------------------

def test_migration_cost_prices_moved_state_per_link():
    def oc(name, state):
        return cm.OperatorCost(name=name, flops_per_event=1.0,
                               bytes_per_event=1.0, out_bytes_per_event=1.0,
                               state_bytes=state)

    ops = [oc("a", 1e6), oc("b", 2e6), oc("c", 4e6)]
    spec = serving_spec()
    old = {"a": "edge0", "b": "edge0", "c": "cloud0"}
    new = {"a": "edge0", "b": "cloud0"}         # b moves, c dropped
    mig = cm.migration_cost(ops, old, new, spec)
    ln = spec.link("edge0", "cloud0")
    assert mig.moves == (("b", "edge0", "cloud0"),)
    assert mig.bytes == 2e6
    assert mig.seconds == pytest.approx(2e6 / ln.bw + ln.latency)
    none = cm.migration_cost(ops, old, dict(old), spec)
    assert none.moves == () and none.bytes == 0.0 and none.seconds == 0.0
    # a move off a pool that already left the spec (crash replan) is
    # recorded but ships nothing — the op restarts from checkpoint
    lost = cm.migration_cost(ops, {"a": "gone0"}, {"a": "cloud0"}, spec)
    assert lost.moves == (("a", "gone0", "cloud0"),)
    assert lost.bytes == 0.0 and lost.seconds == 0.0


def test_replan_decision_carries_priced_migration():
    g = serving_graph_for_placement()
    spec = serving_spec()
    ctl = OffloadController(g, spec, Objective(), cooldown=0)
    ctl.initial_plan(1e3)
    assert ctl.history[-1].migration.moves == ()
    d = ctl.replan(1, 3e3)
    assert d.assignment == {"prefill": "cloud0", "decode": "edge0"}
    (move,) = d.migration.moves
    assert move[0] == "decode" and move[2] == "edge0"
    assert d.migration.bytes == g.op("decode").cost.state_bytes
    assert d.migration.seconds > 0.0
    # hold decisions carry no migration
    d2 = ctl.observe(2, 3e3)
    assert d2.reason == "hold" and d2.migration.moves == ()


# ---------------------------------------------------------------------------
# orchestrator: explicit KV ladder + pytree-aware uplink wire
# ---------------------------------------------------------------------------

def test_stream_job_kv_ladder_governs_admission():
    from repro.core.orchestrator import Orchestrator, StreamJob
    from repro.core.sla import SLA
    job = StreamJob("kv", dim=8, sla=SLA(error_budget=0.5),
                    uplink_codecs=[c.name for c in KV_CODECS])
    orch = Orchestrator(job)
    # kv_latent (bound 0.715) is outside the 0.5 budget; kv_int8 is the
    # cheapest admissible wire and wins the initial pick
    assert orch.codec.name == "kv_int8"
    assert orch.codec_candidates == ["identity", "kv_int8"]
    # every edge<->cloud wire in the priced spec carries the pick
    for e in orch.cluster.edge_pools:
        for c in orch.cluster.cloud_pools:
            assert orch.cluster.link(e.name, c.name).codec == "kv_int8"
            assert orch.cluster.link(c.name, e.name).codec == "kv_int8"
    tight = StreamJob("tight", dim=8, sla=SLA(error_budget=0.0),
                      uplink_codecs=[c.name for c in KV_CODECS])
    assert Orchestrator(tight).codec.name == "identity"


def test_uplink_wire_roundtrips_pytree_channels():
    from repro.core.orchestrator import Orchestrator, StreamJob
    from repro.core.sla import SLA
    job = StreamJob("kv", dim=8, sla=SLA(error_budget=0.5),
                    uplink_codecs=[c.name for c in KV_CODECS])
    orch = Orchestrator(job)
    wire = orch._uplink_fn()
    rng = np.random.default_rng(0)
    kv = {"k": jnp.asarray(rng.normal(size=(4, 8)).astype(np.float32)),
          "idx": jnp.arange(4, dtype=jnp.int32)}
    x = jnp.asarray(rng.normal(size=(8,)).astype(np.float32))
    key = jax.random.PRNGKey(7)
    out = wire({"kv": kv, "x": x, "rng": key})
    # structure survives; int leaves and the rng key pass through raw
    assert set(out) == {"kv", "x", "rng"}
    assert np.array_equal(out["kv"]["idx"], kv["idx"])
    assert np.array_equal(out["rng"], key)
    # float leaves take the int8 wire: close within the codec bound
    for a, b in ((out["kv"]["k"], kv["k"]), (out["x"], x)):
        bound = orch.codec.error_bound * float(jnp.max(jnp.abs(b)))
        assert float(jnp.max(jnp.abs(a - b))) <= bound * 1.001
        assert not np.array_equal(np.asarray(a), np.asarray(b))
    # residuals are keyed per (channel, leaf), so a second wave with the
    # same shapes reuses them instead of re-initializing
    keys = set(orch._uplink_residuals)
    assert all(isinstance(k, tuple) and len(k) == 2 for k in keys)
    assert {"kv", "x"} == {k[0] for k in keys}
    wire({"kv": kv, "x": x, "rng": key})
    assert set(orch._uplink_residuals) == keys
    # codec swaps flush pytree residuals like flat ones
    orch._swap_codec("identity", step=1)
    assert orch._uplink_residuals == {}
