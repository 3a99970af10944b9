"""Smoke run of the system's main paths on a TPU, through the entry points
a user calls, at deployment widths.

  python chip_smoke.py              # one chip: stream graph, stream kernels,
                                    # qwen2-1.5b serving
  python chip_smoke.py --chips 4    # four chips: fsdp training, elastic
                                    # rescale, one- vs four-chip step

Every phase runs in this one process, checks what it produced against the
repository's own reference, and prints one JSON line: what ran, the
shapes, compile seconds (trace + lower + backend compile, from JAX's own
compile events), ``peak_bytes_in_use`` from ``device.memory_stats()``
(the process peak so far), wall seconds, and the comparison. The last line
is ``{"ok": true, "device": {...}}``, printed only when every phase
passed. Without a TPU backend the script exits non-zero before any phase.

Weights come from ``init_params(seed)`` and events from the seeded stream
generators; nothing is read from outside the checkout.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from dataclasses import dataclass

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))


@dataclass(frozen=True)
class Sizes:
    """Deployment widths of every phase (the defaults are what the chip
    runs; a rehearsal on the CPU passes smaller ones)."""
    stream_batches: int = 24
    stream_events: int = 65536
    stream_dim: int = 256
    ef_payload: tuple = (65536, 256)
    cm_depth: int = 4
    cm_width: int = 65536
    cm_ids: int = 65536
    norm_shape: tuple = (65536, 256)
    hash_events: int = 65536
    hash_features: int = 39         # 13 dense + 26 categorical (Criteo)
    hash_dim: int = 1024
    pca_k: int = 64
    smoke_arch: bool = False        # True only for a CPU rehearsal
    requests: int = 8
    prompt_len: int = 512
    new_tokens: int = 32
    max_len: int = 1024
    train_batch: int = 8
    train_seq: int = 512
    train_steps: int = 3
    cut_layers: int = 2


class _CompileClock:
    """Sums the durations of JAX's compile events (trace, lowering,
    backend compile) seen in this process."""

    def __init__(self):
        self.total = 0.0

    def __call__(self, name, secs, **kw):
        if name.startswith("/jax/core/compile/"):
            self.total += secs


CLOCK = _CompileClock()


def _peak_bytes():
    import jax
    stats = [d.memory_stats() for d in jax.local_devices()]
    peaks = [s.get("peak_bytes_in_use") for s in stats if s]
    return max(peaks) if peaks else None


def _has_kernel(jitted, *args, **kw) -> bool:
    """Whether the compiled program of ``jitted`` at these arguments holds
    a Mosaic kernel (the dispatcher is not trusted to say so)."""
    return "tpu_custom_call" in jitted.lower(*args, **kw).compile().as_text()


def run_phase(name, fn, *args):
    """Run one phase and print its line. A phase returns a dict with at
    least ``passed``; exceptions propagate (the run then fails)."""
    c0, t0 = CLOCK.total, time.perf_counter()
    info = fn(*args)
    line = {"phase": name, **info,
            "compile_s": round(CLOCK.total - c0, 3),
            "wall_s": round(time.perf_counter() - t0, 3),
            "peak_bytes_in_use": _peak_bytes()}
    print(json.dumps(line, default=str), flush=True)
    return bool(info["passed"])


def _max_abs(a, b) -> float:
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b))) if a.size else 0.0


# ---------------------------------------------------------------------------
# one chip: the stream orchestrator
# ---------------------------------------------------------------------------

def _stream_data(sz: Sizes):
    from repro.streams.generators import HyperplaneStream
    gen = HyperplaneStream(dim=sz.stream_dim, seed=11,
                           horizon=float(sz.stream_batches * sz.stream_events))
    return [gen.batch(i, sz.stream_events) for i in range(sz.stream_batches)]


def _ramp(sz: Sizes):
    """Offered rate: quiet for the first third, then a spike that pushes
    the learner off the edge (the migration the run must execute)."""
    knee = sz.stream_batches // 3
    return lambda step: 1e3 if step < knee else 5e6


def phase_stream_identity(sz: Sizes, data):
    """StreamJob -> Orchestrator.run over the fan-out graph under a rate
    ramp; every per-batch output must equal the pinned all-cloud run."""
    from repro.core.orchestrator import Orchestrator, StreamJob
    from repro.core.pipeline import fanout_stream_graph

    def job(name):
        return StreamJob(name, dim=sz.stream_dim, max_workers=1,
                         pipeline=fanout_stream_graph(sz.stream_dim))

    m = Orchestrator(job("smoke")).run(data, rate_fn=_ramp(sz),
                                       record_outputs=True)
    ref = Orchestrator(job("ref")).run(data, rate_fn=_ramp(sz),
                                       fixed_frontier=frozenset(),
                                       record_outputs=True)
    diverged = [(i, k) for i, (a, b) in enumerate(zip(m.outputs, ref.outputs))
                for k in sorted(set(a) | set(b))
                if k not in a or k not in b
                or not np.array_equal(a[k], b[k])]
    bitwise = len(m.outputs) == len(ref.outputs) == len(data) and not diverged
    return {"ran": "Orchestrator.run(fanout_stream_graph) vs fixed_frontier "
                   "all-cloud reference, identity codec",
            "shapes": {"batches": len(data), "x": [sz.stream_events,
                                                   sz.stream_dim]},
            "events": m.events, "migrations": m.migrations,
            "frontiers": sorted({tuple(sorted(a)) for a in m.assignments}),
            "check": "outputs bitwise equal to the pinned run",
            "diverged": diverged[:5],
            "passed": bitwise and m.migrations >= 1}


def phase_stream_lossy(sz: Sizes, data):
    """The same job under an SLA that admits a lossy uplink codec: the
    codec trajectory must leave identity, the error-feedback round trip
    must run through the Pallas EF kernels, and each EF kernel must agree
    with the dist.compression reference on one payload."""
    import jax
    import jax.numpy as jnp

    from repro.core.orchestrator import Orchestrator, StreamJob
    from repro.core.pipeline import fanout_stream_graph
    from repro.core.sla import SLA
    from repro.dist import compression as comp
    from repro.kernels import ops as kops

    orch = Orchestrator(StreamJob("lossy", dim=sz.stream_dim, max_workers=1,
                                  sla=SLA(error_budget=11.0),
                                  pipeline=fanout_stream_graph(sz.stream_dim)))
    ef_kernels = (kops.ef_int8_roundtrip, kops.ef_topk_int8_roundtrip)
    before = [f._cache_size() for f in ef_kernels]
    m = orch.run(data, rate_fn=_ramp(sz))
    lossy = sorted(set(m.codecs) - {"identity"})
    # nothing else in this process calls the EF kernels, so their jit
    # caches grow only if the lossy uplink round trip went through them
    dispatched = [f._cache_size() for f in ef_kernels] != before

    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=sz.ef_payload) * 3, jnp.float32)
    res = jnp.asarray(rng.normal(size=sz.ef_payload) * 0.01, jnp.float32)
    k = max(1, round(0.1 * x.size))
    checks = {}
    for name, kern, ref, kw in (
            ("ef_int8_roundtrip", kops.ef_int8_roundtrip,
             lambda r, v: comp.ef_roundtrip(r, v, use_kernel=False), {}),
            ("ef_topk_int8_roundtrip", kops.ef_topk_int8_roundtrip,
             lambda r, v: comp.ef_topk_int8_roundtrip(r, v, k,
                                                      use_kernel=False),
             {"k": k})):
        dec, rout = kern(res, x, **kw)
        decr, routr = jax.jit(ref)(res, x)
        err = {"dec": _max_abs(dec, decr), "residual": _max_abs(rout, routr),
               "ef_identity": _max_abs(dec + rout, x + res)}
        checks[name] = {"max_abs_err": err,
                        "kernel": _has_kernel(kern, res, x, **kw),
                        "within_1e-6": max(err.values()) <= 1e-6}
    passed = (bool(lossy) and dispatched
              and all(c["kernel"] and c["within_1e-6"]
                      for c in checks.values()))
    return {"ran": "Orchestrator.run(fanout_stream_graph), "
                   "SLA(error_budget=11.0); EF kernels vs dist.compression",
            "shapes": {"batches": len(data),
                       "x": [sz.stream_events, sz.stream_dim],
                       "ef_payload": list(sz.ef_payload)},
            "codecs": lossy, "codec_migrations": sum(
                1 for d in m.decisions if ":codec " in d),
            "ef_kernel_dispatched": dispatched,
            "ef": checks,
            "check": "non-identity codec reached; EF kernels within the "
                     "oracle tests' atol 1e-6",
            "passed": passed}


# ---------------------------------------------------------------------------
# one chip: stream kernels through their dispatchers
# ---------------------------------------------------------------------------

def _zipf_ids(n: int, seed: int, vocab: int = 1 << 20):
    from repro.streams.generators import TokenStream
    return TokenStream(vocab_size=vocab, seq_len=n, seed=seed).batch(
        0, 1).data["tokens"][0]


def phase_countmin(sz: Sizes):
    import jax.numpy as jnp

    from repro.kernels import ops as kops
    from repro.kernels import ref
    from repro.streams import sketches as sk

    cm = sk.countmin_init(depth=sz.cm_depth, width=sz.cm_width, seed=3)
    ids1 = jnp.asarray(_zipf_ids(sz.cm_ids, 1))
    ids2 = jnp.asarray(_zipf_ids(sz.cm_ids, 2))
    sk.reset_dispatch_counts()
    cm1 = sk.countmin_add(cm, ids1)
    cm2, est = sk.countmin_add_query(cm1, ids2)
    counts = sk.dispatch_counts()
    seeds = np.asarray(cm.seeds)
    want1 = ref.countmin_ref(ids1, sz.cm_depth, sz.cm_width, seeds)
    want2, want_est = ref.countmin_update_query_ref(ids2, want1, cm.seeds)
    exact = (np.array_equal(np.asarray(cm1.table), np.asarray(want1))
             and np.array_equal(np.asarray(cm2.table), np.asarray(want2))
             and np.array_equal(np.asarray(est), np.asarray(want_est)))
    kernel = (_has_kernel(kops.countmin_update, ids1, depth=sz.cm_depth,
                          width=sz.cm_width, seeds=cm.seeds)
              and _has_kernel(kops.countmin_update_query, ids2, cm1.table,
                              cm.seeds))
    return {"ran": "sketches.countmin_add + countmin_add_query vs "
                   "kernels/ref scatter-add oracles",
            "shapes": {"table": [sz.cm_depth, sz.cm_width],
                       "ids": [sz.cm_ids]},
            "dispatch_counts": counts, "kernel": kernel,
            "check": "tables and estimates exactly equal",
            "passed": exact and kernel and counts == {"pallas": 2,
                                                      "reference": 0}}


def phase_normalize(sz: Sizes):
    import jax.numpy as jnp

    from repro.kernels import ops as kops
    from repro.streams import preprocess as prep

    n, d = sz.norm_shape
    rng = np.random.default_rng(7)
    x = (rng.normal(size=(n, d)) + rng.normal(size=d)).astype(np.float32)
    x[rng.random((n, d)) < 0.15] = np.nan
    x = jnp.asarray(x)
    st = prep.NormState(jnp.asarray(500.0),
                        jnp.asarray(rng.normal(size=d), jnp.float32),
                        jnp.asarray(rng.random(d) * 500 + 50, jnp.float32))
    before = kops.fused_normalize._cache_size()
    st1, y = prep.norm_impute_fused(st, x)
    dispatched = kops.fused_normalize._cache_size() > before
    st_r, y_r = prep.norm_impute_fused(st, x, use_kernel=False)
    kernel = _has_kernel(kops.fused_normalize, x, st.n, st.mean, st.m2)
    close = (np.allclose(np.asarray(st1.mean), np.asarray(st_r.mean),
                         rtol=1e-4, atol=1e-4)
             and np.allclose(np.asarray(st1.m2), np.asarray(st_r.m2),
                             rtol=2e-3, atol=1e-2)
             and np.allclose(np.asarray(y), np.asarray(y_r),
                             rtol=1e-3, atol=1e-3)
             and float(st1.n) == float(st_r.n)
             and not np.isnan(np.asarray(y)).any())
    return {"ran": "preprocess.norm_impute_fused (NaNs imputed) vs the "
                   "impute + Welford jnp composition",
            "shapes": {"x": [n, d]}, "kernel": kernel and dispatched,
            "max_abs_err": {"y": _max_abs(y, y_r),
                            "mean": _max_abs(st1.mean, st_r.mean),
                            "m2": _max_abs(st1.m2, st_r.m2)},
            "check": "within the oracle tests' tolerances",
            "passed": close and kernel and dispatched}


def phase_hash_pipeline(sz: Sizes):
    """hash_op -> pca_op -> sketch_op over the op graph; the hash stage
    must be bitwise the jnp reference, so everything downstream is too."""
    import jax
    import jax.numpy as jnp

    from repro.core.pipeline import OpGraph, hash_op, pca_op, sketch_op
    from repro.kernels import ops as kops
    from repro.kernels import ref
    from repro.streams.generators import TokenStream

    gen = TokenStream(vocab_size=1 << 24, seq_len=sz.hash_features, seed=9)
    rng = np.random.default_rng(9)
    batches = []
    for i in range(2):
        ids = jnp.asarray(gen.batch(i, sz.hash_events).data["tokens"])
        vals = jnp.asarray(rng.normal(
            size=(sz.hash_events, sz.hash_features)), jnp.float32)
        batches.append({"ids": ids, "vals": vals})

    full = OpGraph([hash_op(sz.hash_dim), pca_op(sz.hash_dim, sz.pca_k),
                    sketch_op(sz.pca_k)])
    tail = OpGraph([pca_op(sz.hash_dim, sz.pca_k), sketch_op(sz.pca_k)])
    s_full, s_tail = full.init_states(), tail.init_states()
    # the graph runs each op through its own jitted executable; the hash
    # op's compiled program is what must hold the Mosaic kernel
    b = batches[0]
    graph_kernel = _has_kernel(full._op_fn(0), s_full["hash"], dict(b))
    outputs_equal = True
    for b in batches:
        s_full, out = full.run(s_full, dict(b), frozenset())
        x_ref = ref.hash_features_ref(b["ids"], b["vals"], sz.hash_dim)
        s_tail, out_r = tail.run(s_tail, {"x": x_ref}, frozenset())
        outputs_equal &= np.array_equal(np.asarray(out["x"]),
                                        np.asarray(out_r["x"]))
    states_equal = all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for name in ("pca", "sketch")
        for x, y in zip(jax.tree.leaves(s_full[name]),
                        jax.tree.leaves(s_tail[name])))
    b = batches[0]
    hashed_equal = np.array_equal(
        np.asarray(kops.hash_features(b["ids"], b["vals"], dim=sz.hash_dim)),
        np.asarray(ref.hash_features_ref(b["ids"], b["vals"], sz.hash_dim)))
    kernel = graph_kernel and _has_kernel(kops.hash_features, b["ids"],
                                          b["vals"], dim=sz.hash_dim)
    return {"ran": "OpGraph hash_op -> pca_op -> sketch_op vs the same "
                   "graph fed ref.hash_features_ref",
            "shapes": {"ids": [sz.hash_events, sz.hash_features],
                       "hashed": [sz.hash_events, sz.hash_dim],
                       "pca_k": sz.pca_k, "batches": len(batches)},
            "kernel": kernel, "hashed_equal": hashed_equal,
            "outputs_equal": outputs_equal, "states_equal": states_equal,
            "check": "hash bitwise equal to the reference; pca and sketch "
                     "state bitwise equal downstream",
            "passed": (hashed_equal and outputs_equal and states_equal
                       and kernel)}


# ---------------------------------------------------------------------------
# one chip: qwen2-1.5b serving at published widths
# ---------------------------------------------------------------------------

def _qwen(sz: Sizes):
    from repro.configs import get_config
    return get_config("qwen2-1.5b", smoke=sz.smoke_arch)


def _prompts(cfg, sz: Sizes):
    from repro.streams.generators import TokenStream
    toks = TokenStream(vocab_size=cfg.vocab_size, seq_len=sz.prompt_len,
                       seed=21).batch(0, sz.requests).data["tokens"]
    return [np.asarray(t, np.int32) for t in toks]


def phase_serve(sz: Sizes, eng):
    """The split serving graph at frontier {decode} (cloud prefill, edge
    decode) must emit exactly ServeEngine.run's tokens."""
    from repro.serve.engine import Request
    from repro.serve.ops import serve_wave_batch, serving_graph

    cfg = eng.cfg
    prompts = _prompts(cfg, sz)
    reqs = [Request(i, p, max_new_tokens=sz.new_tokens)
            for i, p in enumerate(prompts)]
    eng.run(reqs)
    want = np.array([r.out_tokens for r in reqs])
    g = serving_graph(eng, prompt_len=sz.prompt_len,
                      max_new_tokens=sz.new_tokens)
    _, out = g.run(g.init_states(), serve_wave_batch(eng, prompts, seed=0),
                   frozenset({"decode"}))
    got = np.asarray(out["out_tokens"])
    return {"ran": f"serve.ops.serving_graph at frontier {{decode}} vs "
                   f"ServeEngine.run, {cfg.name}",
            "shapes": {"requests": sz.requests, "prompt_len": sz.prompt_len,
                       "new_tokens": sz.new_tokens, "batch_size": sz.requests,
                       "max_len": sz.max_len, "layers": cfg.n_layers,
                       "d_model": cfg.d_model},
            "out_tokens_head": got[0, :8].tolist(),
            "check": "out_tokens bitwise equal",
            "passed": got.shape == want.shape and np.array_equal(got, want)}


def phase_prefill_pallas(sz: Sizes, chunked):
    """One prefill with impl="pallas" against the impl="chunked" engine
    the serving phase compiled."""
    from repro.serve.engine import ServeEngine
    from repro.serve.ops import serve_wave_batch

    cfg, params = chunked.cfg, chunked.params
    prompts = _prompts(cfg, sz)
    pallas = ServeEngine(cfg, params, batch_size=sz.requests,
                         max_len=sz.max_len, impl="pallas")
    out = {}
    for impl, eng in (("chunked", chunked), ("pallas", pallas)):
        model_in = {"tokens": serve_wave_batch(eng, prompts)["tokens"]}
        compiled = eng._prefill.lower(params, model_in).compile()
        logits, _ = compiled(params, model_in)
        out[impl] = (np.asarray(logits[:, 0, :cfg.vocab_size], np.float32),
                     "tpu_custom_call" in compiled.as_text())
    (lc, _), (lp, kernel) = out["chunked"], out["pallas"]
    first_equal = np.array_equal(lc.argmax(-1), lp.argmax(-1))
    close = np.allclose(lp, lc, rtol=2e-2, atol=2e-2)
    return {"ran": "model_zoo.prefill impl=pallas vs impl=chunked",
            "shapes": {"tokens": [sz.requests, sz.prompt_len],
                       "max_len": sz.max_len},
            "flash_kernel_in_prefill": kernel,
            "max_abs_logit_diff": _max_abs(lp, lc),
            "check": "greedy first tokens equal; logits within 2e-2",
            "passed": first_equal and close}


# ---------------------------------------------------------------------------
# four chips: fsdp training, elastic rescale, one- vs four-chip step
# ---------------------------------------------------------------------------

def _train_batch(cfg, sz: Sizes, idx: int):
    import jax.numpy as jnp

    from repro.streams.generators import TokenStream
    gen = TokenStream(vocab_size=cfg.vocab_size, seq_len=sz.train_seq,
                      seed=31)
    return {"tokens": jnp.asarray(gen.batch(idx, sz.train_batch)
                                  .data["tokens"])}


def _sharded_init(cfg, mesh, rules, opt):
    """Params and optimizer state created directly in their sharded
    layout (nothing is staged whole on one device)."""
    import jax
    from jax.sharding import NamedSharding

    from repro.dist.api import logical_to_spec
    from repro.models import model_zoo as zoo

    shapes = zoo.param_shapes(cfg)
    shardings = jax.tree.map(
        lambda s, ax: NamedSharding(mesh, logical_to_spec(
            ax, rules["param"], mesh, s.shape)),
        shapes, zoo.param_axes(cfg),
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    params = jax.jit(lambda: zoo.init_params(cfg, 0),
                     out_shardings=shardings)()
    # adamw's state is a dict of parameter-shaped trees (m, v, master)
    state = jax.jit(opt.init, out_shardings={
        k: shardings for k in jax.eval_shape(opt.init, params)})(params)
    return params, state


def _train_steps(cfg, sz: Sizes, workers: int, steps: int):
    """launch/train.py's path in-process: mesh_context + make_train_step
    on a (workers, 1) mesh. Returns (params, per-step metrics, mesh)."""
    import jax
    import jax.numpy as jnp

    from repro.dist import current_mesh, current_rules
    from repro.launch.mesh import mesh_context
    from repro.train.optim import make_optimizer
    from repro.train.train_step import make_train_step

    opt = make_optimizer(cfg, "adamw", lr=3e-4, total_steps=100)
    metrics = []
    with mesh_context(cfg, workers, 1):
        mesh, rules = current_mesh(), current_rules()
        params, state = _sharded_init(cfg, mesh, rules, opt)
        step_fn = jax.jit(make_train_step(cfg, opt), donate_argnums=(0, 1))
        step = jnp.asarray(0)
        for i in range(steps):
            params, state, step, m = step_fn(params, state, step,
                                             _train_batch(cfg, sz, i))
            metrics.append({k: float(v) for k, v in m.items()})
    return params, metrics, mesh


def phase_fsdp_train(sz: Sizes, held: dict):
    """Full-width fsdp training on the (4, 1) mesh; leaves (cfg, params)
    in ``held`` for the rescale phase."""
    import jax

    from repro.configs import get_config
    cfg = get_config("qwen2-1.5b", smoke=sz.smoke_arch).with_overrides(
        recipe="fsdp")
    params, metrics, mesh = _train_steps(cfg, sz, 4, sz.train_steps)
    embed = jax.tree.leaves(params)[0]
    spread = sorted({d.id for d in embed.sharding.device_set})
    finite = all(np.isfinite(m["loss"]) and np.isfinite(m["grad_norm"])
                 for m in metrics)
    held["model"] = (cfg, params)
    return {"ran": f"launch path mesh_context + make_train_step, recipe "
                   f"fsdp, {cfg.name}",
            "shapes": {"mesh": dict(mesh.shape), "batch": sz.train_batch,
                       "seq": sz.train_seq, "layers": cfg.n_layers,
                       "d_model": cfg.d_model},
            "metrics": metrics, "first_leaf_devices": spread,
            "first_leaf_spec": str(embed.sharding.spec),
            "check": "losses and grad norms finite; parameters spread over "
                     "all four chips",
            "passed": finite and len(spread) == 4}


def phase_rescale(cfg, params):
    """One elastic rescale_cycle from 4 to 2 workers: the parameters that
    come back on the 2-chip mesh must equal the saved ones bitwise."""
    import jax

    from repro.dist import elastic as el
    from repro.dist.sharding import build_rules
    from repro.models import model_zoo as zoo

    saved = [np.asarray(x) for x in jax.tree.leaves(params)]
    # the checkpoint stays inside the checkout and is removed afterwards
    with tempfile.TemporaryDirectory(prefix=".chip-smoke-ckpt-",
                                     dir=ROOT) as d:
        tree, mesh = el.rescale_cycle(d, 3, {"params": params},
                                      {"params": zoo.param_axes(cfg)},
                                      build_rules(cfg), 2,
                                      meta={"reason": "chip smoke"})
    back = jax.tree.leaves(tree["params"])
    bitwise = len(back) == len(saved) and all(
        np.array_equal(np.asarray(a), b) for a, b in zip(back, saved))
    devices = sorted({dv.id for x in back for dv in x.sharding.device_set})
    return {"ran": "dist.elastic.rescale_cycle 4 -> 2 workers "
                   "(checkpoint.save -> rebuild_mesh -> reshard_tree)",
            "shapes": {"new_mesh": dict(mesh.shape), "leaves": len(saved),
                       "bytes": int(sum(x.nbytes for x in saved))},
            "devices_after": devices,
            "check": "restored parameters bitwise equal to the saved ones",
            "passed": bitwise and len(devices) == 2}


def phase_cut_compare(sz: Sizes):
    """The same step at published widths cut to a few layers, on one chip
    and on the 4-chip mesh: loss and grad norm agree to bf16 tolerance."""
    from repro.configs import get_config
    cfg = get_config("qwen2-1.5b", smoke=sz.smoke_arch).with_overrides(
        recipe="fsdp", n_layers=sz.cut_layers)
    _, one, _ = _train_steps(cfg, sz, 1, 1)
    _, four, _ = _train_steps(cfg, sz, 4, 1)
    agree = all(np.isclose(four[0][k], one[0][k], rtol=2e-2, atol=2e-2)
                for k in ("loss", "grad_norm"))
    return {"ran": f"one train step on 1 chip vs the (4, 1) mesh, "
                   f"{cfg.name} cut to {cfg.n_layers} layers",
            "shapes": {"batch": sz.train_batch, "seq": sz.train_seq,
                       "layers": cfg.n_layers, "d_model": cfg.d_model},
            "one_chip": one[0], "four_chips": four[0],
            "check": "loss and grad_norm within rtol/atol 2e-2",
            "passed": agree}


# ---------------------------------------------------------------------------

def run_one_chip(sz: Sizes) -> bool:
    from repro.models import model_zoo as zoo
    from repro.serve.engine import ServeEngine

    data = _stream_data(sz)
    ok = run_phase("stream_graph_identity", phase_stream_identity, sz, data)
    ok &= run_phase("stream_graph_lossy_codec", phase_stream_lossy, sz, data)
    del data
    ok &= run_phase("countmin", phase_countmin, sz)
    ok &= run_phase("norm_impute_fused", phase_normalize, sz)
    ok &= run_phase("hash_pca_sketch", phase_hash_pipeline, sz)
    cfg = _qwen(sz)
    eng = ServeEngine(cfg, zoo.init_params(cfg, 0), batch_size=sz.requests,
                      max_len=sz.max_len, seed=0)
    ok &= run_phase("serve_qwen2_1_5b", phase_serve, sz, eng)
    ok &= run_phase("prefill_pallas_vs_chunked", phase_prefill_pallas, sz,
                    eng)
    return ok


def run_four_chips(sz: Sizes) -> bool:
    held = {}
    ok = run_phase("fsdp_train_4chips", phase_fsdp_train, sz, held)
    ok &= run_phase("elastic_rescale_4_to_2", phase_rescale, *held.pop("model"))
    ok &= run_phase("cut_step_1_vs_4_chips", phase_cut_compare, sz)
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the sharded training phases")
    args = ap.parse_args(argv)

    import jax
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: needs a TPU backend, JAX found "
              f"{jax.default_backend()!r}; no phase was run", file=sys.stderr)
        return 1
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} chips, "
              f"found {len(jax.devices())}", file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(CLOCK)

    sz = Sizes()
    ok = run_four_chips(sz) if args.chips == 4 else run_one_chip(sz)
    if not ok:
        print("chip_smoke: a phase failed its check", file=sys.stderr)
        return 1
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
