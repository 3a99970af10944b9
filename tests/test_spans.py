"""Host spans at the program's layer boundaries, read back from a profiler
trace recorded on the CPU: one ``s2ce.op.<name>`` per op per batch inside
``s2ce.execute_batch``, the control primitives, one decode-step span per
generated token after the first, op programs named after their ops, and
outputs that do not depend on whether a profiler is recording."""

import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs.base import get_config
from repro.core import pipeline as pl
from repro.core import spans
from repro.core.orchestrator import Orchestrator, StreamJob
from repro.models import model_zoo as zoo
from repro.serve.engine import Request, ServeEngine
from repro.serve.ops import serve_wave_batch, serving_graph
from repro.streams.events import StreamBatch
from repro.streams.generators import HyperplaneStream

DIM, EVENTS, BATCHES = 4, 64, 3
CFG = get_config("qwen2-1.5b", smoke=True)
NEW_TOKENS = 4
PROMPTS = [np.arange(1, 7, dtype=np.int32), np.arange(3, 11, dtype=np.int32)]


def _recorded(tmp_path, fn):
    """``fn()``'s result and the host events of a trace recorded around it,
    as ``(start_ns, end_ns, name, stats)``."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        result = fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    events = [(e.start_ns, e.end_ns, e.name,
               dict(e.stats) if e.name.startswith(spans.PREFIX) else {})
              for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events
              if e.name.startswith((spans.PREFIX, "PjitFunction("))]
    return result, events


def _named(events, name):
    return [e for e in events if e[2] == name]


def _inside(inner, outer):
    return outer[0] <= inner[0] and inner[1] <= outer[1]


def _stream_run(graph):
    """A fresh job over ``graph`` (whose op executables it shares with
    every other run over ``graph``)."""
    gen = HyperplaneStream(dim=DIM, seed=3, horizon=BATCHES * EVENTS)
    orch = Orchestrator(StreamJob("spans", dim=DIM, pipeline=graph))
    m = orch.run([gen.batch(i, EVENTS) for i in range(BATCHES)],
                 rate_fn=lambda s: 1e3, seed=5, record_outputs=True)
    return orch, m


@pytest.fixture(scope="module")
def stream_trace(tmp_path_factory):
    """A run with no profiler (it also compiles), then one recorded."""
    graph = pl.fanout_stream_graph(DIM)
    off = _stream_run(graph)
    on, events = _recorded(tmp_path_factory.mktemp("stream"),
                           lambda: _stream_run(graph))
    return on, events, off


def test_each_op_spans_once_per_batch_inside_its_batch(stream_trace):
    (orch, _), events, _ = stream_trace
    batches = _named(events, "s2ce.execute_batch")
    assert sorted(b[3]["step"] for b in batches) == list(range(BATCHES))
    assert all(b[3]["events"] == EVENTS for b in batches)
    for name in orch.pipeline.names:
        ops = _named(events, f"s2ce.op.{name}")
        assert len(ops) == BATCHES, name
        homes = [b[3]["step"] for o in ops for b in batches if _inside(o, b)]
        assert sorted(homes) == list(range(BATCHES)), name


def test_staging_and_control_spans_are_recorded(stream_trace):
    _, events, _ = stream_trace
    batches = _named(events, "s2ce.execute_batch")
    for inner in ("s2ce.stage_batch", "s2ce.drift_check",
                  "s2ce.sla_observe"):
        got = _named(events, inner)
        assert len(got) == BATCHES, inner
        assert all(any(_inside(e, b) for b in batches) for e in got)
    for name in ("topology", "observe", "apply", "elastic"):
        got = _named(events, f"s2ce.control.{name}")
        assert sorted(e[3]["step"] for e in got) == list(range(BATCHES))
        # the control pass runs after its batch, outside it
        assert not any(_inside(e, b) for e in got for b in batches)


def _known(full_name):
    """Whether ``full_name``, as it reads in a trace, is one of
    ``spans.NAMES``; a name ending in ``.`` is a family."""
    if not full_name.startswith(spans.PREFIX):
        return False
    name = full_name[len(spans.PREFIX):]
    return any(name.startswith(n) and len(name) > len(n)
               if n.endswith(".") else name == n for n in spans.NAMES)


def test_every_program_span_is_a_known_name(stream_trace):
    _, events, _ = stream_trace
    names = {e[2] for e in events if e[2].startswith(spans.PREFIX)}
    assert names and all(_known(n) for n in names)
    assert not _known("s2ce.op.") and not _known("bench.execute")


def test_op_programs_carry_their_op_names(stream_trace):
    (orch, _), events, _ = stream_trace
    dispatched = {e[2] for e in events}
    for name in orch.pipeline.names:
        assert f"PjitFunction({name})" in dispatched, name
    assert "PjitFunction(fn)" not in dispatched


def test_stream_outputs_are_bitwise_the_same_while_recording(stream_trace):
    (orch_on, m_on), _, (orch_off, m_off) = stream_trace
    assert len(m_on.outputs) == len(m_off.outputs) == BATCHES
    for a, b in zip(m_on.outputs, m_off.outputs):
        assert a.keys() == b.keys()
        for k in a:
            assert np.array_equal(a[k], b[k]), k
    on, off = (jax.tree.leaves(o.states) for o in (orch_on, orch_off))
    assert len(on) == len(off)
    for a, b in zip(on, off):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def _serve_graph(eng, waves=2):
    graph = serving_graph(eng, prompt_len=10, max_new_tokens=NEW_TOKENS)
    orch = Orchestrator(StreamJob("serve-spans", pipeline=graph))
    orch.begin(1.0, seed=0)
    keep = []
    run = orch.pipeline.run

    def run_and_keep(states, batch, frontier=(), uplink=None):
        states, out = run(states, batch, frontier, uplink=uplink)
        keep.append(np.asarray(out["out_tokens"]))
        return states, out

    orch.pipeline.run = run_and_keep
    for k in range(waves):
        orch.execute_batch(k, StreamBatch(
            data=serve_wave_batch(eng, PROMPTS, seed=k)))
    return np.stack(keep)


def _serve_engine(eng, waves=2):
    eng.rng = jax.random.PRNGKey(0)
    reqs = [Request(i, PROMPTS[i % 2], max_new_tokens=NEW_TOKENS)
            for i in range(2 * waves)]
    eng.run(reqs)
    return np.array([r.out_tokens for r in reqs])


SERVE_PATHS = {"graph": _serve_graph, "engine": _serve_engine}


@pytest.fixture(scope="module")
def served():
    """One engine for every serving run, and each path's tokens served
    with no profiler (which also compiles what the recorded runs use)."""
    eng = ServeEngine(CFG, zoo.init_params(CFG, 0), batch_size=2,
                      max_len=32, seed=0)
    return eng, {p: run(eng) for p, run in SERVE_PATHS.items()}


@pytest.mark.parametrize("path", sorted(SERVE_PATHS))
def test_serving_spans_one_decode_step_per_token_after_the_first(
        tmp_path, served, path):
    waves = 3
    eng, _ = served
    _, events = _recorded(tmp_path, lambda: SERVE_PATHS[path](eng, waves))
    steps = _named(events, "s2ce.serve.decode_step")
    assert len(steps) == waves * (NEW_TOKENS - 1)
    assert sorted(e[3]["i"] for e in steps) == sorted(
        list(range(NEW_TOKENS - 1)) * waves)
    prefills = _named(events, "s2ce.serve.prefill")
    assert [(e[3]["rows"], e[3]["len"]) for e in prefills] == [(2, 8)] * waves
    assert _named(events, "s2ce.serve.gather")
    if path == "graph":
        batches = _named(events, "s2ce.execute_batch")
        assert len(batches) == waves
        for b in batches:
            inner = [e for e in steps if _inside(e, b)]
            assert len(inner) == NEW_TOKENS - 1
            assert len([e for e in _named(events, "s2ce.op.decode")
                        if _inside(e, b)]) == 1


@pytest.mark.parametrize("path", sorted(SERVE_PATHS))
def test_served_tokens_are_bitwise_the_same_while_recording(
        tmp_path, served, path):
    eng, tokens = served
    on, _ = _recorded(tmp_path, lambda: SERVE_PATHS[path](eng))
    off = tokens[path]
    assert on.shape[-1] == NEW_TOKENS
    assert np.array_equal(on, off)
