"""Stream cells: a ``StreamJob`` over an operator graph, driven through the
``Orchestrator`` step primitives exactly as ``Orchestrator.run`` composes
them (``begin`` once; per batch ``execute_batch``, ``topology_step``,
``controller.observe``, ``apply_decision``, ``elastic_step``), with the
traffic's offered rate as the rate the controller is told.

The loop only puts due times and spans around those calls. A batch is due
when its last event arrives; it is done when its outputs are ready.
"""

from __future__ import annotations

import gc
import time
from dataclasses import dataclass, field
from typing import Dict, List

import jax
import numpy as np

from bench import arrivals, datagen
from bench.cells import load_json
from bench.harness import Item, Tracer, judge

OUTPUT_KEYS = ("score", "p", "err", "drifted", "alert")
CODEC_SAMPLES = 3          # window batches whose decoded uplink is compared
CODEC_SAMPLE_SPAN = 6      # ... drawn from the first this many


@dataclass
class System:
    orch: object
    ring: list
    offered: float
    ring_index: List[int] = field(default_factory=list)
    outputs: Dict[int, dict] = field(default_factory=dict)
    decoded: Dict[int, dict] = field(default_factory=dict)
    keep_decoded: set = field(default_factory=set)
    step: int = 0
    window_first: int = 0


def build_job(cell):
    from repro.core.costmodel import ClusterSpec, Link, Resource
    from repro.core.orchestrator import Orchestrator, StreamJob
    from repro.core.pipeline import fanout_stream_graph
    from repro.core.sla import SLA
    cfg = cell.config
    g = cfg["graph"]
    graph = fanout_stream_graph(
        g["dim"], sample_rate=g["sample_rate"],
        drift_detector=g["drift_detector"], reservoir_k=g["reservoir_k"],
        anomaly_threshold=g["anomaly_threshold"])
    cluster = ClusterSpec([Resource(**p) for p in cfg["cluster"]["pools"]],
                          [Link(**ln) for ln in cfg["cluster"]["links"]])
    job = StreamJob(name=cell.name, dim=g["dim"], sla=SLA(**cfg["sla"]),
                    cluster=cluster, pipeline=graph,
                    sample_rate=g["sample_rate"],
                    drift_detector=g["drift_detector"], workers=1,
                    max_workers=cfg["max_workers"])
    return Orchestrator(job)


def _instrument(sys_: System) -> None:
    """Keep each batch's outputs (device references, no copy) and the
    decoded uplink payload of the sampled batches."""
    orch = sys_.orch
    run_graph = orch.pipeline.run

    def run_and_keep(states, batch, frontier=(), uplink=None):
        states, out = run_graph(states, batch, frontier, uplink=uplink)
        sys_.outputs[sys_.step] = {k: out[k] for k in OUTPUT_KEYS}
        return states, out

    orch.pipeline.run = run_and_keep
    wire = orch._uplink
    if wire is not None:
        def wire_and_keep(env):
            out = wire(env)
            if sys_.step in sys_.keep_decoded:
                sys_.decoded[sys_.step] = {
                    k: v for k, v in out.items() if k != "rng"
                    and np.issubdtype(np.dtype(v.dtype), np.floating)}
            return out
        orch._uplink = wire_and_keep


def _precompile(orch, x, y) -> None:
    """Compile every program a batch of this shape runs under the plan in
    force, without the orchestrator's telemetry seeing it: the op graph on
    the initial states (it is functional, nothing is kept) and the codec
    on each channel that crosses the uplink. A first batch that compiled
    inside ``execute_batch`` would count as a latency violation in the
    SLA window and move the controller off its plan."""
    import jax.numpy as jnp
    bd = {"x": jnp.asarray(x), "y": jnp.asarray(y),
          "rng": jax.random.PRNGKey(0)}

    def codec_only(env):
        for k, v in env.items():
            if k != "rng" and jnp.issubdtype(v.dtype, jnp.floating):
                jax.block_until_ready(orch.codec.roundtrip(
                    orch.codec.init_residual(v), v))
        return env

    _, out = orch.pipeline.run(orch.states, bd, orch.frontier,
                               uplink=None if orch.codec.lossless
                               else codec_only)
    jax.block_until_ready(out)


def _one_batch(sys_: System, run, ring_at: int) -> float:
    """One batch through the step primitives; returns when its outputs
    are ready, with the control pass done after."""
    from repro.streams.events import StreamBatch
    orch, k = sys_.orch, sys_.step
    with run.spans.span("bench.generate"):
        x, y = sys_.ring[ring_at]
        batch = StreamBatch(data={"x": x, "y": y})
    with run.spans.span("bench.execute"):
        rate = orch.execute_batch(k, batch)
        jax.block_until_ready(sys_.outputs[k])
    done = time.perf_counter()
    with run.spans.span("bench.control"):
        orch.topology_step(k, sys_.offered)
        d = orch.controller.observe(k, sys_.offered, orch.sla)
        orch.apply_decision(k, d)
        orch.elastic_step(k, sys_.offered, rate)
    sys_.ring_index.append(ring_at)
    sys_.step += 1
    return done


def setup(cell, run) -> System:
    tr, g = cell.traffic, cell.config["graph"]
    data = cell.config["data"]
    if int(data["numAtts"]) != int(g["dim"]):
        raise ValueError(f"the job is {g['dim']} wide, the data "
                         f"{data['numAtts']}")
    orch = build_job(cell)
    ring = datagen.hyperplane(run.seed, int(tr["ring_batches"]),
                              int(tr["batch_events"]), data)
    sys_ = System(orch=orch, ring=ring, offered=float(tr["rate"]))
    orch.begin(sys_.offered, seed=run.seed)
    _precompile(orch, *ring[0])
    _instrument(sys_)
    for _ in range(int(tr["warmup_batches"])):
        _one_batch(sys_, run, sys_.step % len(ring))
    # the drift response runs eagerly when an alarm fires: compile it now
    for op in orch.pipeline.ops:
        if op.on_drift is not None:
            jax.block_until_ready(op.on_drift(orch.states[op.name]))
    rng = np.random.default_rng((run.seed & (2**63 - 1), 9))
    sys_.window_first = sys_.step
    sys_.keep_decoded = {sys_.step + int(i) for i in rng.choice(
        CODEC_SAMPLE_SPAN, CODEC_SAMPLES, replace=False)}
    return sys_


def window(sys_: System, run, trace_dir) -> None:
    tr = run.cell.traffic
    tracer = Tracer(run, trace_dir, int(tr["trace_items"]))
    t0 = run.open_window()
    run.items = [Item(it.index, t0 + it.due, it.sizes) for it in
                 arrivals.schedule(tr, run.seed, run.seconds)]
    drain = t0 + run.seconds + float(tr["drain_s"])
    for item in run.items:
        now = time.perf_counter()
        if now >= drain:
            break
        if now < item.due:
            with run.spans.span("bench.wait_arrival"):
                time.sleep(item.due - now)
        tracer.before(item)
        item.start = time.perf_counter()
        item.done = _one_batch(sys_, run, sys_.step % len(sys_.ring))
        item.ok = True
        tracer.after(item)
    tracer.close()
    run.close_window()
    m = sys_.orch.metrics
    first = max(sys_.window_first - 1, 0)
    codecs, plans = m.codecs[first:], m.assignments[first:]
    run.counters.update({
        "batches_in_window": len(run.completed()),
        "events_in_window": sum(it.sizes["events"]
                                for it in run.completed()),
        "codec": m.codecs[-1], "edge_ops": sorted(m.assignments[-1]),
        "window_codec_changes": sum(a != b for a, b in zip(codecs,
                                                           codecs[1:])),
        "window_frontier_changes": sum(a != b for a, b in zip(plans,
                                                              plans[1:])),
        "drift_alarms": m.drift_alarms, "graph_compiles":
            sys_.orch.pipeline.compiles,
        "decisions": m.decisions[-5:]})


def _rel(a, b) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    if a.shape != b.shape:
        return float("nan")
    scale = max(float(np.max(np.abs(b))) if b.size else 0.0, 1e-30)
    return float(np.max(np.abs(a - b))) / scale if b.size else 0.0


def _state_leaves(name: str, state) -> list:
    leaves = jax.tree.leaves(state)
    if name == "anomaly":            # (projections, bin edges, counts, n)
        return [leaves[0], leaves[2], leaves[3]]
    return leaves


def compare(program: dict, ref: dict) -> Dict[str, float]:
    """The numbers ``correct`` is decided on."""
    p_err = score_err = 0.0
    flags = 0
    for step, out in program["outputs"].items():
        r = ref["outputs"][step]
        p_err = max(p_err, float(np.max(np.abs(
            out["p"].astype(np.float64) - np.asarray(r["p"], np.float64)))))
        score_err = max(score_err, float(np.max(np.abs(
            out["score"].astype(np.float64)
            - np.asarray(r["score"], np.float64)))))
        flags += int(np.sum(out["err"] != np.asarray(r["err"])))
        flags += int(bool(out["drifted"]) != bool(r["drifted"]))
        flags += int(bool(out["alert"]) != bool(r["alert"]))
    state_err = 0.0
    for name, st in program["states"].items():
        a = _state_leaves(name, st)
        b = _state_leaves(name, ref["states"].get(name, ()))
        if len(a) != len(b):
            return dict.fromkeys(("p_err", "score_err", "flag_mismatches",
                                  "state_err"), float("nan"))
        for x, y in zip(a, b):
            state_err = max(state_err, _rel(x, y))
    numbers = {"p_err": p_err, "score_err": score_err,
               "flag_mismatches": float(flags), "state_err": state_err}
    if program["decoded"]:           # a lossy uplink codec is in the plan
        numbers["codec_err"] = max(
            _rel(v, ref["decoded"][step][k])
            for step, env in program["decoded"].items()
            for k, v in env.items())
    return numbers


def program_record(sys_: System) -> dict:
    """Host copies of what the program produced, so its state can go."""
    m = sys_.orch.metrics
    window_steps = range(sys_.window_first, sys_.step)
    return {
        "outputs": {s: {k: np.asarray(v) for k, v in sys_.outputs[s].items()}
                    for s in window_steps},
        "decoded": {s: {k: np.asarray(v) for k, v in env.items()}
                    for s, env in sys_.decoded.items()},
        "states": jax.tree.map(np.asarray, dict(sys_.orch.states)),
        "plans": [(frozenset(a), c) for a, c in zip(m.assignments,
                                                    m.codecs)],
        "steps": list(range(sys_.step)),
        "ring_index": list(sys_.ring_index),
    }


def replay(cell, run, prog: dict, ring, dt=None) -> dict:
    """The reference over the batches the program processed, at its step
    numbers and plans, in ``dt`` (the configuration's float32 by
    default), as host arrays."""
    import jax.numpy as jnp
    ref = cell.reference().replay(
        cell.config, (ring[i] for i in prog["ring_index"]), prog["steps"],
        prog["plans"], root_seed=run.seed, keep=prog["outputs"].keys(),
        keep_decoded=prog["decoded"].keys(),
        dt=jnp.float32 if dt is None else dt)
    return jax.tree.map(np.asarray, ref)


def limits(cell) -> Dict[str, float]:
    return load_json(cell.bench_dir / "limits" / f"{cell.name}.json")[
        "limits"]


def release(sys_: System):
    """Host copies of the program's results; the program's state goes."""
    prog = program_record(sys_)
    sys_.orch = None
    sys_.outputs.clear()
    sys_.decoded.clear()
    gc.collect()
    return prog


def check(sys_: System, run):
    prog = release(sys_)
    ring = sys_.ring
    numbers = compare(prog, replay(run.cell, run, prog, ring))
    return judge(numbers, limits(run.cell))


def calibrate(sys_: System, run, control: bool) -> dict:
    """The program's numbers, and the control's: the reference in bfloat16
    put in the program's place, both against the float32 reference."""
    import jax.numpy as jnp
    ring = sys_.ring
    prog = release(sys_)
    ref = replay(run.cell, run, prog, ring)
    out = {"program": compare(prog, ref), "control": None}
    if control:
        out["control"] = compare(replay(run.cell, run, prog, ring,
                                        dt=jnp.bfloat16), ref)
    return out
