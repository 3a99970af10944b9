"""Serving cells: ``StreamJob(pipeline=serving_graph(engine, ...))`` driven
through the ``Orchestrator`` step primitives, one wave per ``StreamBatch``.

Requests arrive open-loop. Whenever the orchestrator is free the loop takes
up to a wave's worth of queued requests, in arrival order, pads the wave
with filler rows, and builds its tokens as ``serve_wave_batch`` does; with
an empty queue it waits for the next arrival. A request is done when its
wave's ``out_tokens`` are ready; its first ``output_len`` tokens are what
it asked for.
"""

from __future__ import annotations

import collections
import gc
import time
from dataclasses import dataclass, field
from typing import Dict, List

import jax
import numpy as np

from bench import arrivals, datagen
from bench.cells import load_json
from bench.harness import Item, Tracer, judge

SAMPLE = 16      # requests compared with the reference, the longest among them


@dataclass
class System:
    orch: object
    engine: object
    offered: float
    step: int = 0
    outputs: Dict[int, object] = field(default_factory=dict)
    waves: List[dict] = field(default_factory=list)


def arch_config(cfg: dict):
    """The program's config for ``cfg`` (keys of the model's config.json),
    run as the configuration states: its widths, depth, rope base, norm
    epsilon and precision."""
    from repro.configs.base import get_config
    base = get_config(cfg["program_arch"])
    d, h = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    dtype = {"bfloat16": "bfloat16", "float32": "float32"}[
        cfg["torch_dtype"]]
    return base.with_overrides(
        n_layers=int(cfg["num_hidden_layers"]), d_model=d, n_heads=h,
        n_kv_heads=int(cfg["num_key_value_heads"]),
        d_head=int(cfg.get("head_dim") or d // h),
        d_ff=int(cfg["intermediate_size"]), vocab_size=int(cfg["vocab_size"]),
        vocab_pad_multiple=256, qkv_bias=True, mlp_act="silu_glu",
        norm_type="rmsnorm", norm_eps=float(cfg["rms_norm_eps"]),
        pos_embed="rope", rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        param_dtype=dtype, compute_dtype=dtype, kv_cache_dtype=dtype)


def program_params(w: dict, arch):
    """Lay the seeded weights out as the program's parameter tree, and
    check the layout against the program's own shapes."""
    from repro.models import model_zoo as zoo
    tree = {
        "embed": {"tok": w["embed"]},
        "final_norm": {"scale": w["final_norm"]},
        "prefix": [],
        "stack": [{
            "norm1": {"scale": w["attn_norm"]},
            "mixer": {k: w[k] for k in ("wq", "wk", "wv", "wo",
                                        "bq", "bk", "bv")},
            "norm2": {"scale": w["mlp_norm"]},
            "mlp": {k: w[k] for k in ("w_gate", "w_up", "w_down")},
        }],
    }
    want = zoo.param_shapes(arch)
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError("the program's parameter layout changed: "
                         f"{jax.tree.structure(want)}")
    return tree


def _prompt(run, item) -> np.ndarray:
    return datagen.zipf_tokens(run.seed, item.index, item.sizes["prompt_len"],
                               int(run.cell.config["vocab_size"]))


def _one_wave(sys_: System, run, prompts: List[np.ndarray]) -> float:
    from repro.serve.ops import serve_wave_batch
    from repro.streams.events import StreamBatch
    orch, k = sys_.orch, sys_.step
    sv = run.cell.config["serve"]
    with run.spans.span("bench.form_wave"):
        rows = prompts + [np.zeros(1, np.int32)] * (sv["batch_size"]
                                                     - len(prompts))
        batch = StreamBatch(data=serve_wave_batch(sys_.engine, rows))
    with run.spans.span("bench.execute"):
        rate = orch.execute_batch(k, batch)
        jax.block_until_ready(sys_.outputs[k])
    done = time.perf_counter()
    with run.spans.span("bench.control"):
        orch.topology_step(k, sys_.offered)
        d = orch.controller.observe(k, sys_.offered, orch.sla)
        orch.apply_decision(k, d)
        orch.elastic_step(k, sys_.offered, rate)
    sys_.step += 1
    return done


def setup(cell, run) -> System:
    from repro.core.orchestrator import Orchestrator, StreamJob
    from repro.core.sla import SLA
    from repro.serve.engine import ServeEngine
    from repro.serve.ops import serving_graph
    from repro.serve.sampling import SamplingParams
    cfg, tr = cell.config, cell.traffic
    sv = cfg["serve"]
    arch = arch_config(cfg)
    params = program_params(cell.reference().weights(cfg, run.seed), arch)
    engine = ServeEngine(arch, params, batch_size=sv["batch_size"],
                         max_len=sv["max_len"], impl=sv["impl"],
                         sampling=SamplingParams(greedy=True))
    graph = serving_graph(engine, prompt_len=sv["prompt_len"],
                          max_new_tokens=sv["max_new_tokens"])
    orch = Orchestrator(StreamJob(cell.name, sla=SLA(**cfg["sla"]),
                                  pipeline=graph, workers=1,
                                  max_workers=cfg["max_workers"]))
    sys_ = System(orch=orch, engine=engine,
                  offered=float(tr["rate"]))
    orch.begin(sys_.offered, seed=run.seed)
    run_graph = orch.pipeline.run
    # compile each wave shape the traffic sends outside the orchestrator's
    # telemetry (a compile inside execute_batch reads as an SLA violation),
    # then run one wave of each through the step primitives
    from repro.serve.ops import serve_wave_batch
    lengths = arrivals.size_values(tr, "prompt_len")
    for s in lengths:
        rows = [np.ones(s, np.int32)] + [np.zeros(1, np.int32)] * (
            sv["batch_size"] - 1)
        _, out = run_graph(orch.states, serve_wave_batch(engine, rows),
                           orch.frontier)
        jax.block_until_ready(out)

    def run_and_keep(states, batch, frontier=(), uplink=None):
        states, out = run_graph(states, batch, frontier, uplink=uplink)
        sys_.outputs[sys_.step] = out["out_tokens"]
        return states, out

    orch.pipeline.run = run_and_keep
    for s in lengths:
        _one_wave(sys_, run, [np.ones(s, np.int32)])
    sys_.outputs.clear()
    return sys_


def window(sys_: System, run, trace_dir) -> None:
    tr = run.cell.traffic
    wave = int(run.cell.config["serve"]["batch_size"])
    tracer = Tracer(run, trace_dir, int(tr["trace_items"]))
    t0 = run.open_window()
    run.items = [Item(it.index, t0 + it.due, it.sizes) for it in
                 arrivals.schedule(tr, run.seed, run.seconds)]
    drain = t0 + run.seconds + float(tr["drain_s"])
    queue: collections.deque = collections.deque()
    nxt = 0
    while True:
        now = time.perf_counter()
        if now >= drain:
            break
        while nxt < len(run.items) and run.items[nxt].due <= now:
            queue.append(run.items[nxt])
            nxt += 1
        if not queue:
            if nxt == len(run.items):
                break
            with run.spans.span("bench.wait_arrival"):
                time.sleep(max(run.items[nxt].due - now, 0.0))
            continue
        members = [queue.popleft() for _ in range(min(wave, len(queue)))]
        prompts = [_prompt(run, it) for it in members]
        info = {"step": sys_.step, "items": members,
                "prompt_len": max(len(p) for p in prompts),
                "prompts": prompts}
        tracer.before(info)
        start = time.perf_counter()
        for it in members:
            it.start, it.wave = start, sys_.step
        done = _one_wave(sys_, run, prompts)
        for it in members:
            it.done, it.ok = done, True
        info["done"] = done
        sys_.waves.append(info)
        tracer.after(info)
    tracer.close()
    run.close_window()
    run.counters.update({
        "waves_in_window": len(sys_.waves),
        "requests_done": sum(len(w["items"]) for w in sys_.waves),
        "requests_due": len(run.items),
        "queued_at_close": len(queue) + len(run.items) - nxt,
        "wave_fill": (sum(len(w["items"]) for w in sys_.waves)
                      / max(1, wave * len(sys_.waves))),
        "edge_ops": sorted(sys_.orch.metrics.assignments[-1])
        if sys_.orch.metrics.assignments else [],
        "codec": sys_.orch.codec.name})


def sample_rows(sys_: System, run) -> List[dict]:
    """The compared requests: the longest one finished and others drawn
    from the seed, each with its wave's padded prompt and its tokens."""
    done = [(w, j) for w in sys_.waves for j in range(len(w["items"]))]
    if not done:
        return []
    size = lambda wj: wj[0]["prompt_len"] + wj[0]["items"][wj[1]].sizes[
        "output_len"]
    longest = max(range(len(done)), key=lambda i: size(done[i]))
    rng = np.random.default_rng((run.seed & (2**63 - 1), 11))
    rest = [i for i in range(len(done)) if i != longest]
    pick = [longest] + list(rng.choice(rest, min(SAMPLE - 1, len(rest)),
                                       replace=False))
    rows = []
    for i in pick:
        w, j = done[int(i)]
        S = w["prompt_len"]
        p = w["prompts"][j]
        prompt = np.zeros(S, np.int32)
        prompt[S - len(p):] = p
        out = np.asarray(sys_.outputs[w["step"]])[j]
        rows.append({"prompt": prompt, "own": p,
                     "served": out[:w["items"][j].sizes["output_len"]]})
    return rows


def limits(cell) -> Dict[str, float]:
    return load_json(cell.bench_dir / "limits" / f"{cell.name}.json")[
        "limits"]


def release(sys_: System, run) -> List[dict]:
    rows = sample_rows(sys_, run)
    sys_.orch = sys_.engine = None
    sys_.outputs.clear()
    gc.collect()
    return rows


def _widest(gaps) -> float:
    return float(max(g.max() for g in gaps)) if gaps else float("nan")


def check(sys_: System, run):
    rows = release(sys_, run)
    gaps = run.cell.reference().served_gaps(run.cell.config, run.seed,
                                            rows)["served"] if rows else []
    return judge({"served_gap": _widest(gaps)}, limits(run.cell))


def calibrate(sys_: System, run, control: bool) -> dict:
    """The program's number, and the control's: the reference in float8 put
    in the program's place, read at the same prompts and tokens. Beside
    them, not compared with a limit, the served tokens' gap against the
    reference given each request's own prompt, unpadded: what a request
    would get served alone."""
    rows = release(sys_, run)
    ref = run.cell.reference()
    g = ref.served_gaps(run.cell.config, run.seed, rows,
                        control="fp8" if control else None)
    padded = sum(len(r["own"]) < len(r["prompt"]) for r in rows)
    alone = _widest(ref.served_gaps(
        run.cell.config, run.seed,
        [{"prompt": r["own"], "served": r["served"]} for r in rows]
    )["served"]) if padded else _widest(g["served"])
    return {"program": {"served_gap": _widest(g["served"])},
            "control": ({"served_gap": _widest(g["control"])}
                        if control else None),
            "served_gap_unpadded": alone, "padded_rows": padded,
            "compared_tokens": int(sum(len(x) for x in g["served"]))}
